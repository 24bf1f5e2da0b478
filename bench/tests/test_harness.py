"""The harness end to end on the CPU at the opt-mini size: a sound run is
correct, and a run whose timed path is broken underneath is not."""
import dataclasses
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import pytest

from bench import run

ROOT = run.ROOT
BENCH = {"workloads": [{"name": "mini.closed", "config": "opt-mini",
                        "traffic": "mini-closed", "chips": 1}],
         "end_to_end": [{"name": "out_tok_s"}, {"name": "itl_p99_ms"},
                        {"name": "setup_s"}],
         "per_layer": []}
CELL = BENCH["workloads"][0]
SEED = 2 ** 32 + 77


@pytest.fixture(autouse=True)
def _cache_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))


def _run(hooks=None):
    return run.run_cell(BENCH, CELL, SEED, 4.0, False, on_chip=False,
                        hooks=hooks)


def test_sound_run_is_correct():
    res = _run()
    assert res["correct"] is True
    assert res["failed"] == 0 and res["attempted"] > 0
    assert set(res["metrics"]) == {"out_tok_s", "itl_p99_ms", "setup_s"}
    assert set(res["compared"]) == {"mean_logit_gap", "kv_code_steps"}
    for v in res["compared"].values():
        assert v["value"] <= v["limit"]


def _alter_tokens(sysm):
    """A token altered where it is produced: every fourth decode step
    serves each row the next id after the one the program chose."""
    dec = sysm.engine._decode
    vocab = sysm.cfg["vocab_size"]
    calls = [0]

    def broken(*a):
        nxt, ok, cache = dec(*a)
        calls[0] += 1
        if calls[0] % 4 == 0:
            nxt = (nxt + 1) % vocab
        return nxt, ok, cache
    sysm.engine._decode = broken


def _state_unchanged(sysm):
    """A step that returns its state unchanged: decode drops its K/V
    writes and the lengths it advanced."""
    dec = sysm.engine._decode

    def broken(params, tok, cache, keys, poison):
        nxt, ok, _ = dec(params, tok, cache, keys, poison)
        return nxt, ok, cache
    sysm.engine._decode = broken


def _codes_altered(sysm):
    """Stored K/V codes altered where they are produced: the chunk
    program writes its prompt positions' codes one step off in the first
    layer."""
    chk = sysm.engine._chunk

    def broken(params, tokens, chunk_len, cache, offset):
        logits, out = chk(params, tokens, chunk_len, cache, offset)
        one = jnp.int8(1)
        return logits, dataclasses.replace(out, k=out.k.at[0].add(one),
                                           v=out.v.at[0].add(one))
    sysm.engine._chunk = broken


@pytest.mark.parametrize("fault, number", [
    (_alter_tokens, "mean_logit_gap"),
    (_state_unchanged, "kv_code_steps"),
    (_codes_altered, "kv_code_steps")])
def test_broken_timed_path_is_not_correct(fault, number):
    res = _run(hooks=fault)
    assert res["correct"] is False
    got = res["compared"][number]
    assert got["value"] > got["limit"]


def test_no_tpu_exits_nonzero_without_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "bench/run.py", "--workload",
                        "opt-6.7b.decode", "--seed", "3", "--seconds", "1",
                        "--trace", "0"], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=300)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr
