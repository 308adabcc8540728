"""``tools/tier1_times.py`` on a small recorded junit file: seconds by
file (a class's cases are its file's), the skips, the long cases, the
wall the sums give, and the exit code that says "split this file"."""

import importlib.util
import os

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SAMPLE = os.path.join(REPO, "tests", "tier1_junit_sample.xml")


@pytest.fixture(scope="module")
def tool():
    spec = importlib.util.spec_from_file_location(
        "tier1_times", os.path.join(REPO, "tools", "tier1_times.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_a_file_is_a_sixth_of_the_limit(tool):
    assert (tool.LIMIT_S, tool.WORKERS, tool.FILE_S) == (1470.0, 6, 245.0)
    assert tool.file_of("tests.test_x.TestY") == "tests/test_x.py"
    assert tool.file_of("tests.test_x") == "tests/test_x.py"


def test_the_sums_by_file_and_what_is_over_the_line(tool, capsys):
    got = tool.read(SAMPLE)
    by_file = {name: (n, round(s, 3), skipped)
               for s, n, name, skipped in got["files"]}
    assert by_file == {
        "tests/test_llama_flagship.py": (2, 369.11, 0),
        "tests/test_mla_moe_trunk.py": (4, 299.278, 0),
        "tests/test_api_completeness.py": (3, 2.676, 1)}
    assert [case for _, case in got["cases"]] == [
        "tests/test_llama_flagship.py::test_graft_entry",
        "tests/test_mla_moe_trunk.py::test_two_steps_match_the_reference"
        "[loss_rel_gap.step0]",
        "tests/test_mla_moe_trunk.py::"
        "test_a_program_altered_in_one_place_fails[top_one]",
        "tests/test_mla_moe_trunk.py::"
        "test_a_program_altered_in_one_place_fails[no_yarn]"]
    # the longest file, not the sum over six workers, is this run's wall
    assert got["wall_s"] == got["longest_s"] == pytest.approx(369.11)
    assert got["total_s"] / tool.WORKERS < got["wall_s"]
    # xdist's order: by number of tests, largest first — three files on
    # six workers all start at 0
    assert got["scheduled_s"] == pytest.approx(369.11)
    assert [(n, name) for _, _, n, name in got["last"]] == [
        (2, "tests/test_llama_flagship.py"),
        (4, "tests/test_mla_moe_trunk.py"),
        (3, "tests/test_api_completeness.py")]
    assert got["over"] == ["tests/test_llama_flagship.py",
                           "tests/test_mla_moe_trunk.py"]
    assert tool.main(["tier1_times.py", SAMPLE]) == 1
    out = capsys.readouterr().out
    assert "OVER 245 s (a sixth of the limit): tests/test_mla_moe_trunk.py" \
        in out and "(1 skipped)" in out
    assert tool.main(["tier1_times.py"]) == 2


def test_a_file_of_few_cases_is_handed_out_last(tool):
    """Seven files on six workers: the one-case file waits for the first
    worker to come free, however long it is."""
    files = [(10.0 * n, n, f"tests/test_{n}.py", 0) for n in range(2, 8)] \
        + [(300.0, 1, "tests/test_one_long_case.py", 0)]
    ends = tool.schedule(files)
    assert ends[0] == (320.0, 20.0, 1, "tests/test_one_long_case.py")
    assert sorted(start for _, start, _, _ in ends) == [0.0] * 6 + [20.0]


def test_a_run_with_no_long_file_exits_zero(tool, tmp_path):
    short = tmp_path / "short.xml"
    with open(SAMPLE) as f:
        short.write_text("\n".join(
            line for line in f.read().splitlines()
            if "test_graft_entry" not in line and "loss_rel_gap" not in line))
    got = tool.read(str(short))
    assert not got["over"] and tool.main(["tier1_times.py", str(short)]) == 0
