"""The state-space mixer (``hybrid_trunk._mamba_mixer``) over the scan's and
the convolution's kernels (in the interpreter here): it reads in place
where the shapes let it and gives the same values where not — bit for bit
(``tests/test_ssd_scan_in_place.py`` holds the kernels alone to that).
"""

import functools

import pytest

import jax
import jax.numpy as jnp

import paddle_tpu  # noqa: F401  (enables x64 before arrays exist)
from paddle_tpu.ops.pallas import causal_conv, ssd_scan as kernel
from test_ssd_scan import F32
from test_ssd_scan_in_place import _same


def _mixer(heads, state, seed=13):
    """One Mamba-2 mixer at toy widths, heads of 64, two chunks a row."""
    from paddle_tpu.models import hybrid_trunk
    from paddle_tpu.models.llama_pretrain import LlamaPretrainConfig
    cfg = LlamaPretrainConfig(
        vocab_size=64, hidden_size=64, intermediate_size=128,
        num_hidden_layers=1, num_attention_heads=2, num_key_value_heads=2,
        layer_types=("mamba",), mamba_n_heads=heads, mamba_d_head=64,
        mamba_d_state=state, mamba_chunk_size=128, dtype=F32,
        param_dtype=F32)
    names = [nm for nm in hybrid_trunk.kind_shapes(cfg, "mamba")
             if nm not in ("ln1", "ln2", "w_gate", "w_up", "w_down")]
    keys = jax.random.split(jax.random.PRNGKey(seed), len(names) + 1)
    bp = {nm: hybrid_trunk.init_leaf(cfg, k, "mamba", nm, 1)[0]
          for nm, k in zip(names, keys)}
    v = jax.random.normal(keys[-1], (2, 256, cfg.hidden_size), F32)
    return cfg, bp, v, functools.partial(hybrid_trunk._mamba_mixer, cfg=cfg)


# heads of 64, state: xBC starts on a lane tile and the state is one
# (both in place); starts half a tile in (the convolution on a slice);
# a state of 64 (the scan on slices, the convolution in place)
@pytest.mark.parametrize("heads,state,conv_in_place,scan_in_place", [
    (2, 128, True, True), (1, 32, False, False), (2, 64, True, False)])
def test_mixer_reads_in_place_where_it_can_and_the_same_values_where_not(
        monkeypatch, heads, state, conv_in_place, scan_in_place):
    """The mixer as the train step calls it, against itself with both
    ``takes`` of the in-place forms answering no: every piece sliced out
    for the kernels, as before they took offsets.  Output and every
    gradient bit for bit."""
    from paddle_tpu.models import hybrid_trunk
    cfg, bp, v, mixer = _mixer(heads, state)
    d_inner, conv, _ = hybrid_trunk.mamba_dims(cfg)
    zxbcdt = v @ bp["w_in"]
    assert causal_conv.takes(zxbcdt, bp["conv_w"], d_inner) == conv_in_place
    assert causal_conv.takes(zxbcdt[..., d_inner:d_inner + conv],
                             bp["conv_w"])
    assert kernel.takes_xbc(jnp.zeros((2, 2, 128, conv)), heads,
                            state) == scan_in_place

    def run():
        out, vjp = jax.vjp(mixer, bp, v)
        dbp, dv = vjp(jnp.cos(out * 3.0))
        return [out, dv] + [dbp[nm] for nm in sorted(dbp)]
    got = run()
    takes = causal_conv.takes
    monkeypatch.setattr(causal_conv, "takes",
                        lambda x, w, offset=0: not offset and takes(x, w))
    monkeypatch.setattr(kernel, "takes_xbc", lambda *a: False)
    _same(got, run(), ["out", "dv"] + sorted(bp))
