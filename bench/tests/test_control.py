"""The control of ``correct`` fails at a size a test run can hold: the
plain reference computed in float8 (the next precision below the
configuration's bfloat16), put in the program's place, comes out not
correct by the harness's own check and limits on every seed where the
program comes out correct (CPU, opt-mini x mini-closed; the limits and
their readings are in bench/limits/mini.closed.json)."""
import pytest

from bench import run

CELL = {"name": "mini.closed", "config": "opt-mini",
        "traffic": "mini-closed", "chips": 1}
BENCH = {"workloads": [CELL], "end_to_end": [], "per_layer": []}


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


@pytest.mark.parametrize("seed", [2, 6, 10])
def test_control_is_not_correct(seed):
    limits = run.load_limits("mini.closed")
    res = run.run_cell(BENCH, CELL, seed, 8.0, False, on_chip=False,
                       control=True)
    got = res["readings"]
    assert got["served_tokens"] >= 500 and got["kv_requests"] >= 1
    assert res["correct"] is True
    assert res["control_correct"] is False
    for name, lim in limits.items():
        assert got["program"][name] <= lim["limit"]
    assert got["control"]["kv_code_steps"] > limits["kv_code_steps"]["limit"]
    assert got["altered"]["mean_logit_gap"] > \
        limits["mean_logit_gap"]["limit"]
