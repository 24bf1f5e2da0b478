"""Classification of the decode program's operations by their named
scopes (``bench/phases.py``), on the paged decode program compiled for a
described TPU v5e at the opt-mini width: every operation of the layer
loop gets one class, the loop's slices and restacks of the KV pool count
as ``kv_pool``, and its per-layer copies of the packed weights that feed
``w4a8_matmul`` as ``weight_slice``.  The topology is described inside a
fixture (only one process may load the TPU library)."""
import gzip
import os

import jax
import jax.numpy as jnp
import pytest

from bench import load, model as bm, phases, system

DATA = os.path.join(os.path.dirname(__file__), "data")
LOOP_CLASSES = {"kernels", "kv_pool", "weight_slice", "decoder",
                "attn.qkv", "attn.flash", "attn.out", "mlp"}


@pytest.fixture(scope="module")
def compiled():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding
    from repro.serve.engine import Engine
    from repro.serve.quantized import QuantizedModel
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler / library lock held elsewhere
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    chip = SingleDeviceSharding(topo.devices[0])
    cfg = bm.load_config("opt-mini")
    mix = load.load_mix("mini-closed")
    mc, qc = bm.program_config(cfg)
    eng = Engine(QuantizedModel(mc, qc, kernel_mode="pallas"),
                 system.build_params(cfg, 5),
                 system.serve_config(cfg, mix, load.max_context(mix)))
    args = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=chip),
        (eng.params, eng._last_tok, eng._kv.cache, eng._idle_keys,
         eng._zero_poison))
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        hlo = eng._decode.lower(*args).compile().as_text()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        compilation_cache.reset_cache()
    instrs = phases.instructions(hlo)
    return instrs, phases.classify(instrs, *phases.array_shapes(eng))


def _loop(instrs):
    bodies = phases.decoder_loops(instrs)
    assert len(bodies) == 1
    return {n: i for n, i in instrs.items() if i["comp"] in bodies}


NO_WORK = ("parameter", "get-tuple-element", "tuple", "bitcast",
           "constant")


def test_every_loop_operation_gets_one_class(compiled):
    instrs, cls = compiled
    loop = _loop(instrs)
    assert set(cls) == set(instrs)
    got = {n: cls[n] for n, i in loop.items() if i["opcode"] not in NO_WORK}
    assert set(got.values()) <= LOOP_CLASSES
    assert {"kernels", "kv_pool", "weight_slice"} <= set(got.values())


def test_pool_slices_and_restacks_are_kv_pool(compiled):
    instrs, cls = compiled
    loop = _loop(instrs)
    moves = [n for n, i in loop.items() if i["opcode"] == "fusion"
             and i["op_name"].endswith(("/decoder/while/body/dynamic_slice",
                                        "/decoder/while/body/"
                                        "dynamic_update_slice"))
             and i["shapes"][0][0] in ("s8", "f32")]
    # K and V codes and scales: one slice and one restack each
    assert len(moves) >= 8
    assert {cls[n] for n in moves} == {"kv_pool"}
    writes = [n for n, i in loop.items()
              if "/kv_write/" in i["op_name"]]
    assert writes and {cls[n] for n in writes} == {"kv_pool"}


def test_kernel_operand_slices_are_weight_slice(compiled):
    instrs, cls = compiled
    loop = _loop(instrs)
    fed = [o for n, i in loop.items()
           if i["kernel"] and n.startswith("w4a8_matmul")
           for o in i["operands"][2:]]
    # six matmuls a layer, each fed codes, scales and zero points
    assert len(fed) == 18
    assert {cls[o] for o in fed} == {"weight_slice"}
    assert [n for n, c in cls.items()
            if c == "weight_slice" and n in loop] == [
        n for n in loop if n in fed]


def test_parent_program_has_no_scopes():
    """The decode program recorded on the chip before the scopes existed:
    nothing is classified, so the readers report nothing."""
    with gzip.open(os.path.join(DATA, "decode-6.7b.decode_hlo.txt.gz"),
                   "rt") as f:
        instrs = phases.instructions(f.read())
    assert len(instrs) > 1000
    assert phases.classify(instrs, set(), set()) == {}


def test_idle_split_by_innermost_span():
    """Each idle nanosecond goes to the innermost ``serve.*`` span open
    over it, or to the caller between steps."""
    spans = [("serve.step", 10, 50), ("serve.decode.dispatch", 20, 30),
             ("serve.decode.wait", 30, 45), ("serve.emit", 45, 50),
             ("serve.step", 60, 70), ("serve.deadlines", 61, 62),
             ("bench.step", 0, 90)]
    red = {"ops": [("a", 0, 15), ("b", 25, 40), ("c", 65, 80)],
           "lo": 0, "hi": 90}
    got = {k: round(v * 1e9) for k, v in
           phases.idle_by_phase(red, spans).items()}
    assert got == {"serve.step": 9, "serve.decode.dispatch": 5,
                   "serve.decode.wait": 5, "serve.emit": 5,
                   "serve.deadlines": 1, "caller, between steps": 20}
