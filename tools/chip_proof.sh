#!/usr/bin/env bash
# Proof that what git commits starts on the chip.  Run it on the machine
# with ONE TPU chip, from the root of a checkout or of a `git archive`
# copy:
#
#     bash tools/chip_proof.sh [OUT_DIR]      # default: chiprun_out
#
# 1. chip_smoke.py cold: JAX_COMPILATION_CACHE_DIR unset, so the cache is
#    <checkout>/.jax_cache, emptied first;
# 2. the same again, warm: it must compile NOTHING (cache_misses=0) and
#    print the same losses, tokens and step counts;
# 3. chip_smoke.py with the environment as it came (the driver's run);
# 4. the on-chip kernel parity lane, tests/test_pallas_tpu.py;
# 5. chip_smoke.py alone in an empty directory: it must fail.
# One process at a time holds the chip.  Exit code 0 iff all five held.
set -u
out=${1:-chiprun_out}
mkdir -p "$out"
out=$(cd "$out" && pwd)
fail=0
facts() {   # what must repeat run to run: losses, tokens, step counts
    grep -E "trainer: step [0-9]+ |decode steps|greedy tokens|divergence" "$1" |
        sed -E 's/ [0-9]+ ms$//; s/, compile_s=.*//'
}

rm -rf .jax_cache
for run in cold warm; do
    env -u JAX_COMPILATION_CACHE_DIR python chip_smoke.py \
        > "$out/smoke_$run.log" 2> "$out/smoke_$run.err"
    rc=$?
    echo "smoke $run rc=$rc"
    [ $rc -eq 0 ] || fail=1
    grep -E "compile cache at|step compiled|decode steps|all phases" \
        "$out/smoke_$run.log"
    tail -n 1 "$out/smoke_$run.log"
done
grep -q "compile cache at $PWD/.jax_cache" "$out/smoke_cold.log" ||
    { echo "FAIL: unset, the cache is not <checkout>/.jax_cache"; fail=1; }
grep -Eq "all phases passed.* cache_misses=0$" "$out/smoke_warm.log" ||
    { echo "FAIL: the warm run compiled something"; fail=1; }
if [ "$(facts "$out/smoke_cold.log")" != "$(facts "$out/smoke_warm.log")" ]
then
    echo "FAIL: cold and warm runs differ"
    diff <(facts "$out/smoke_cold.log") <(facts "$out/smoke_warm.log")
    fail=1
fi

python chip_smoke.py > "$out/smoke_asis.log" 2> "$out/smoke_asis.err"
rc=$?
echo "smoke as-is rc=$rc (JAX_COMPILATION_CACHE_DIR=${JAX_COMPILATION_CACHE_DIR-unset})"
[ $rc -eq 0 ] || fail=1
grep -E "compile cache at|all phases" "$out/smoke_asis.log"
tail -n 1 "$out/smoke_asis.log"

PADDLE_TPU_TESTS_ON_TPU=1 python -m pytest tests/test_pallas_tpu.py -q \
    -p no:cacheprovider > "$out/onchip_kernels.txt" 2>&1
rc=$?
echo "kernel lane rc=$rc"
tail -n 5 "$out/onchip_kernels.txt"
{ [ $rc -eq 0 ] && tail -n 1 "$out/onchip_kernels.txt" |
    grep -Eq "^[0-9]+ passed in "; } ||
    { echo "FAIL: a kernel test failed or was skipped"; fail=1; }

bare=$(mktemp -d)
cp chip_smoke.py "$bare/"
(cd "$bare" && python chip_smoke.py > out.txt 2> err.txt)
rc=$?
echo "bare directory rc=$rc (must not be 0); stdout: $(cat "$bare/out.txt")"
tail -n 2 "$bare/err.txt"
{ [ $rc -ne 0 ] && ! grep -q '"ok"' "$bare/out.txt"; } || fail=1
rm -rf "$bare"

echo "chip_proof: $([ $fail -eq 0 ] && echo PASSED || echo FAILED)"
exit $fail
