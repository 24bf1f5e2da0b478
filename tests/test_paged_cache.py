"""Paged KV cache: kernel/oracle parity, allocator accounting, and
paged-vs-linear decode equivalence.

Contract under test (DESIGN.md §9):
  * ``ops.flash_decode(..., page_table=...)`` in interpret mode is
    BIT-identical to ``ref.flash_decode_paged_ref`` under jit for every
    (kv_bits, GQA group, page_size, ragged cur_len) combination — including
    cur_len 0 / 1 / exact page boundaries — over *shuffled, non-contiguous*
    page assignments;
  * the XLA gather fallback (``auto`` off-TPU) matches to fp tolerance;
  * a sequence holds exactly ``ceil(len / page_size)`` pages (free-list
    accounting) and unallocated pages drop token writes;
  * ``QuantizedModel.decode_step`` over a ``PagedKVCache`` is bit-identical
    (ref mode, tile == page) to the linear-cache decode; the fp
    ``transformer`` paged path matches its linear path;
  * the fused paged path materializes NO fp logical-cache intermediate
    (jaxpr traversal; the gather fallback is the positive control).
"""
import dataclasses
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import kernel_conformance as kc
from repro.configs import get_config
from repro.core.quantizer import QuantConfig
from repro.kernels import flash_decode as fd
from repro.kernels import ops
from repro.models import build_model
from repro.serve import kv_cache as kvc
from repro.serve.quantized import QuantizedModel, quantize_lm_packed


# ---------------------------------------------------------------------------
# kernel parity (the acceptance sweep)
# ---------------------------------------------------------------------------

def _block_edge_lens(page_size, g):
    """Lengths at the edges of the kernel's page blocks: empty, one token,
    a page boundary, a block boundary and one past it, and a row whose
    live pages are no multiple of the block — with the block size the
    kernel picks for these shapes (asserted, so the rows do span blocks)."""
    ppb = max(1, fd.BLOCK_TOKENS // (page_size * g))
    lens = [0, 1, page_size, ppb * page_size, ppb * page_size + 1,
            (2 * ppb + 1) * page_size - 5]
    return lens, ppb


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", kc.GQA_GROUPS)
@pytest.mark.parametrize("page_size,rows", [
    *(pytest.param(n, "ragged", id=str(n)) for n in kc.KV_BLOCKS),
    *(pytest.param(n, "block_edges", id=f"{n}-block_edges")
      for n in kc.KV_BLOCKS)])
def test_paged_interpret_bit_identical_to_ref(kv_bits, g, page_size, rows):
    """Ragged cur_len in one batch — empty row, single token, exact page
    boundary, and a mid-page tail; or rows at the edges of the kernel's
    page blocks — all bit-identical through the page-table-walking grid
    (shuffled pages, a -1 tail past each row's own)."""
    b, hkv, d = 4, 2, 32
    lens = [0, 1, page_size, 2 * page_size + 7]
    if rows == "block_edges":
        lens, ppb = _block_edge_lens(page_size, g)
        b = len(lens)
    key = jax.random.PRNGKey(kv_bits * 10 + g + page_size)
    q, kv, pt, _ = kc.make_paged_inputs(key, b, hkv, g, d, page_size, lens,
                                        kv_bits)
    cur = jnp.asarray(lens, jnp.int32)
    if rows == "block_edges":
        assert fd.pages_per_block(kv, g, pt.shape[1]) == ppb
    kc.assert_interpret_matches_ref(ops.flash_decode, q, kv, cur,
                                    page_table=pt)


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
@pytest.mark.parametrize("g", [1, 2, 4])
def test_paged_result_independent_of_pages_per_block(kv_bits, g):
    """The block size is the kernel's own choice, not part of its result:
    one, three and the chosen number of pages a grid step give the same
    bits, since pages update the online softmax one at a time in order."""
    b, hkv, d, ps = 4, 2, 32, 16
    lens = [0, 1, 5 * ps, 7 * ps + 3]
    q, kv, pt, _ = kc.make_paged_inputs(jax.random.PRNGKey(40 + g), b, hkv,
                                        g, d, ps, lens, kv_bits)
    cur = jnp.asarray(lens, jnp.int32)
    q4 = q.reshape(b, hkv, g, d)
    k, v, ks, vs = kv if len(kv) == 4 else (*kv, None, None)

    def run(ppb):
        return np.asarray(jax.jit(functools.partial(
            fd._flash_decode_pages, scale=None, ppb=ppb, name="flash_decode",
            interpret=True))(q4, k, v, pt, cur, ks, vs))

    one = run(1)
    np.testing.assert_array_equal(one, run(3))
    np.testing.assert_array_equal(
        one, run(fd.pages_per_block(kv, g, pt.shape[1])))


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
def test_paged_matches_gather_fallback_and_oracle(kv_bits):
    """Fused paged kernel vs the XLA page-gather fallback (mode='auto'
    off-TPU) vs a from-scratch numpy softmax over the gathered cache."""
    b, hkv, g, d, ps = 3, 2, 2, 32, 16
    lens = [1, 19, 41]
    q, kv, pt, deq = kc.make_paged_inputs(jax.random.PRNGKey(kv_bits), b,
                                          hkv, g, d, ps, lens, kv_bits)
    cur = jnp.asarray(lens, jnp.int32)
    y_int = kc.assert_matches_fallback(ops.flash_decode, q, kv, cur,
                                       page_table=pt)
    k_full = kc.gathered(deq[0], pt)
    v_full = kc.gathered(deq[1], pt)
    y_np = kc.softmax_oracle(q, k_full, v_full, lens)
    np.testing.assert_allclose(np.asarray(y_int), y_np, rtol=1e-4, atol=1e-4)


def test_paged_interpret_smoke():
    """Tiny paged interpret run (the CI fast-lane smoke)."""
    q, kv, pt, _ = kc.make_paged_inputs(jax.random.PRNGKey(0), 2, 2, 2, 8, 8,
                               [3, 14], 8)
    y = ops.flash_decode(q, kv, jnp.asarray([3, 14], jnp.int32),
                         page_table=pt, mode="interpret")
    assert y.shape == (2, 1, 4, 8) and bool(jnp.isfinite(y).all())


def test_paged_zero_length_rows_return_zeros():
    q, kv, pt, _ = kc.make_paged_inputs(jax.random.PRNGKey(1), 2, 2, 2, 16, 16,
                               [0, 30], 8)
    cur = jnp.asarray([0, 30], jnp.int32)
    for mode in ("interpret", "ref", "auto"):
        y = ops.flash_decode(q, kv, cur, page_table=pt, mode=mode)
        np.testing.assert_array_equal(np.asarray(y[0]),
                                      np.zeros_like(np.asarray(y[0])))
        assert bool(jnp.any(y[1] != 0))


def test_paged_rejects_bad_shapes():
    q, kv, pt, _ = kc.make_paged_inputs(jax.random.PRNGKey(2), 2, 2, 1, 8, 8,
                               [4, 8], 16)
    with pytest.raises(ValueError, match="page_table"):
        ops.flash_decode(q, kv, jnp.asarray([4, 8]), page_table=pt[:1],
                         mode="ref")


# ---------------------------------------------------------------------------
# allocator + write semantics
# ---------------------------------------------------------------------------

def test_page_allocator_accounting():
    al = kvc.PageAllocator(num_pages=6, max_pages_per_seq=3, max_batch=2)
    assert al.num_free == 6
    assert len(al.allocate(0, 2)) == 2 and al.num_in_use == 2
    assert al.allocate(0, 2) is None          # 2 + 2 > max_pages_per_seq
    assert al.num_in_use == 2                 # failed alloc left no residue
    assert al.allocate(0, 1) is not None      # exactly at the per-seq cap
    assert al.allocate(1, 4) is None          # pool has only 3 left
    assert al.allocate(1, 3) is not None
    assert al.num_free == 0
    assert al.free(0) == 3 and al.num_free == 3
    assert al.free(1) == 3 and al.num_free == 6
    assert al.owned[0] == [] and al.owned[1] == []


def test_page_allocator_double_free_raises():
    """Integrity guard: a page both owned and on the free list means the
    free list would hand one page to two sequences — free() must raise,
    not silently extend the corruption (DESIGN.md §12)."""
    al = kvc.PageAllocator(num_pages=4, max_pages_per_seq=4, max_batch=2)
    pages = al.allocate(0, 2)
    al.free(0)
    al.owned[0] = list(pages)        # stale owned list resurrected after
    with pytest.raises(kvc.PageIntegrityError, match="double-free"):
        al.free(0)                   # its pages went back to the free list


def test_page_allocator_shared_page_raises():
    """Integrity guard: a page in ``owned[slot]`` the refcounts never
    credited to that slot is a corrupted handoff — freeing through it
    would recycle KV its real owner is actively reading."""
    al = kvc.PageAllocator(num_pages=4, max_pages_per_seq=4, max_batch=2)
    pages = al.allocate(0, 2)
    al.allocate(1, 1)
    al.owned[1].append(pages[1])              # simulate a corrupted handoff
    with pytest.raises(kvc.PageIntegrityError, match="also owned by"):
        al.free(1)


def test_page_allocator_adopt_refcounts():
    """Legitimate sharing (prefix cache, DESIGN.md §14): adopt() adds
    readers instead of pages, free() releases a shared page only at
    refcount 0, and a refcount-0 cached page is revived off the free
    list by a later adopt."""
    al = kvc.PageAllocator(num_pages=6, max_pages_per_seq=4, max_batch=3)
    pages = al.allocate(0, 2)
    assert al.adopt(1, pages) and al.adopt(2, pages)
    assert al.refcount(pages[0]) == 3 and al.num_in_use == 2
    assert al.free(0) == 2 and al.num_free == 4      # readers keep them live
    assert al.refcount(pages[0]) == 2
    assert al.free(1) == 2 and al.free(2) == 2
    assert al.num_free == 6 and al.refcount(pages[0]) == 0
    # refcount-0 pages parked as cached sit at the free-list FRONT:
    # fresh allocations recycle everything else first
    assert al.adopt(0, pages)
    al.free(0, cached=frozenset(pages))
    got = al.allocate(1, 4)
    assert got is not None and not (set(got) & set(pages))
    # ...and adopt revives them from the free list when matched
    assert al.adopt(2, pages)
    assert al.num_free == 0 and al.refcount(pages[0]) == 1
    al.free(1), al.free(2)
    assert sorted(al.free_list) == list(range(6))


def test_page_allocator_adopt_respects_page_table_cap():
    """adopt() is all-or-nothing against max_pages_per_seq, like
    allocate()."""
    al = kvc.PageAllocator(num_pages=8, max_pages_per_seq=3, max_batch=2)
    pages = al.allocate(0, 3)
    assert al.allocate(1, 1) is not None
    assert not al.adopt(1, pages)             # 1 + 3 > max_pages_per_seq
    assert al.refcount(pages[0]) == 1         # nothing adopted
    assert al.adopt(1, pages[:2])


def test_paged_cache_verify_audits_device_table():
    """PagedCache.verify(): full conservation + device/host mirror audit —
    the post-trace invariant every fault test leans on."""
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    store = kvc.PagedCache(model, max_batch=2, max_len=32, page_size=8)
    assert store.reserve(0, 11) and store.reserve(1, 5)
    store.verify()                            # healthy state passes
    broken = dataclasses.replace(
        store.cache, page_table=store.cache.page_table.at[0, 0].set(
            int(store.cache.page_table[1, 0])))
    store.cache = broken
    with pytest.raises(kvc.PageIntegrityError, match="page-table row"):
        store.verify()


def test_paged_cache_integrity_checked_free_catches_misdirection():
    """Debug-mode free (integrity_checks): a device page-table row that
    diverged from the host allocator must refuse the free."""
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    store = kvc.PagedCache(model, max_batch=2, max_len=32, page_size=8,
                           integrity_checks=True)
    assert store.reserve(0, 11)
    store.free(0)                             # healthy free passes
    assert store.reserve(0, 11)
    store.cache = dataclasses.replace(
        store.cache, page_table=store.cache.page_table.at[0, 1].set(-1))
    with pytest.raises(kvc.PageIntegrityError, match="diverged"):
        store.free(0)


def test_pages_track_sequence_length():
    """Free-list accounting: a sequence of length n owns exactly
    ceil(n / page_size) pages through reserve + ensure_append growth."""
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    ps = 8
    store = kvc.PagedCache(model, max_batch=2, max_len=64, page_size=ps)
    assert store.reserve(0, 11)               # ceil(11/8) == 2 pages
    assert len(store.allocator.owned[0]) == 2
    for n in range(11, 40):
        assert store.ensure_append(0, n)
        assert len(store.allocator.owned[0]) == int(np.ceil((n + 1) / ps))
    n_used = store.allocator.num_in_use
    assert n_used == int(np.ceil(40 / ps))
    store.free(0)
    assert store.allocator.num_free == store.allocator.num_pages


def test_unallocated_page_drops_write():
    """token_write_dest resolves unallocated pages / at-capacity sequences
    to an out-of-bounds index — the scatter drops the write."""
    pt = jnp.asarray([[2, -1], [0, 1]], jnp.int32)
    ps, num_pages = 4, 3
    # seq 0 at len 4 -> logical page 1 unallocated; seq 1 at len 7 -> page 1
    dest = kvc.token_write_dest(pt, jnp.asarray([4, 7]), ps, num_pages)
    assert int(dest[0]) == num_pages * ps          # OOB -> dropped
    assert int(dest[1]) == 1 * ps + 3
    # at capacity (len == mpps * ps) the write drops too
    dest = kvc.token_write_dest(pt, jnp.asarray([8, 8]), ps, num_pages)
    assert int(dest[0]) == int(dest[1]) == num_pages * ps
    pool = jnp.zeros((num_pages * ps, 2))
    out = pool.at[dest].set(jnp.ones((2, 2)))
    np.testing.assert_array_equal(np.asarray(out), np.asarray(pool))


# ---------------------------------------------------------------------------
# model integration: paged decode == linear decode
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def micro():
    cfg = get_config("llama-micro")
    model = build_model(cfg)
    return cfg, model, model.init(jax.random.PRNGKey(0))


@pytest.mark.parametrize("kv_bits", kc.KV_BITS)
def test_quantized_paged_decode_bit_identical_to_linear(micro, kv_bits):
    """ref mode, one tile == one page on both layouts: the paged decode
    step must produce BIT-identical logits and cache contents."""
    cfg, model, params = micro
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=kv_bits)
    packed = quantize_lm_packed(params, cfg, qcfg)
    ps = 8
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=ps)
    toks = jax.random.randint(jax.random.PRNGKey(3), (2, 10), 0,
                              cfg.vocab_size)
    lg, lin = qm.prefill(packed, {"tokens": toks}, max_len=32)
    store = kvc.PagedCache(qm, max_batch=2, max_len=32, page_size=ps)
    for slot in range(2):
        assert store.reserve(slot, 10)
        store.splice(slot, lin, slot, 10)
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    cache_l, cache_p = lin, store.cache
    for _ in range(3):
        dl, cache_l = jax.jit(qm.decode_step)(packed, tok, cache_l)
        dp, cache_p = jax.jit(qm.decode_step)(packed, tok, cache_p)
        np.testing.assert_array_equal(np.asarray(dl), np.asarray(dp))
        tok = jnp.argmax(dl[:, -1:], -1).astype(jnp.int32)
    np.testing.assert_array_equal(np.asarray(cache_l["len"]),
                                  np.asarray(cache_p.lens))
    # the paged pool holds exactly the linear cache rows, page-permuted
    s = int(cache_p.lens[0])
    gathered = np.stack([
        np.asarray(cache_p.k[li])[np.maximum(np.asarray(cache_p.page_table),
                                             0)].reshape(2, -1,
                                                         *cache_p.k.shape[3:])
        for li in range(cache_p.k.shape[0])])
    np.testing.assert_array_equal(gathered[:, :, :s],
                                  np.asarray(cache_l["k"])[:, :, :s])


def test_fp_paged_decode_matches_linear(micro):
    """The fp transformer paged path (XLA page gather off-TPU) matches the
    linear decode_attention path."""
    cfg, model, params = micro
    toks = jax.random.randint(jax.random.PRNGKey(5), (2, 12), 0,
                              cfg.vocab_size)
    lg, lin = model.prefill(params, {"tokens": toks}, max_len=32)
    store = kvc.PagedCache(model, max_batch=2, max_len=32, page_size=8)
    for slot in range(2):
        assert store.reserve(slot, 12)
        store.splice(slot, lin, slot, 12)
    tok = jnp.argmax(lg[:, -1:], -1).astype(jnp.int32)
    dl, _ = jax.jit(model.decode_step)(params, tok, lin)
    dp, cache_p = jax.jit(model.decode_step)(params, tok, store.cache)
    np.testing.assert_allclose(np.asarray(dl), np.asarray(dp),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_array_equal(np.asarray(cache_p.lens),
                                  np.full((2,), 13))


def test_paged_cache_is_jit_stable_pytree(micro):
    """PagedKVCache round-trips jit (static page_size, array leaves); a
    host-side page-table update does not retrigger compilation."""
    cfg, model, params = micro
    qcfg = QuantConfig(w_bits=8, a_bits=16, group_size=32, lwc=False,
                       kv_bits=8)
    qm = QuantizedModel(cfg, qcfg, kernel_mode="ref", flash_block_kv=8)
    packed = quantize_lm_packed(params, cfg, qcfg)
    store = kvc.PagedCache(qm, max_batch=2, max_len=32, page_size=8)
    store.reserve(0, 3)
    store.reserve(1, 5)
    cache = dataclasses.replace(store.cache,
                                lens=jnp.asarray([3, 5], jnp.int32))
    step = jax.jit(qm.decode_step)
    tok = jnp.zeros((2, 1), jnp.int32)
    _, c2 = step(packed, tok, cache)
    n0 = step._cache_size()
    # host-side table mutation (new pytree, same shapes) -> no recompile
    c3 = dataclasses.replace(c2, page_table=c2.page_table.at[0, 1].set(7))
    step(packed, tok, c3)
    assert step._cache_size() == n0


def test_paged_cache_shardings_resolve(micro):
    """Serving mesh layout (DESIGN.md §13): pool tensors shard their
    KV-*head* dim over the TP axis ('cache_heads' -> model) so pages stay
    device-local; page tables and lens are REPLICATED (host-authored
    scheduler state), and the dryrun's shardings_for rebuilds a
    PagedKVCache-shaped sharding tree for jit in_shardings."""
    cfg, _, _ = micro
    from jax.sharding import Mesh, PartitionSpec as P

    from repro import sharding
    from repro.launch.dryrun import shardings_for
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, kv_bits=8)
    qm = QuantizedModel(cfg, qcfg)
    specs = qm.paged_cache_specs(batch=4, num_pages=16, page_size=8,
                                 max_pages_per_seq=4)
    axes = qm.cache_logical_axes(specs)
    devs = np.array(jax.devices()[:1]).reshape(1, 1)
    mesh = Mesh(devs, ("data", "model"))
    sh = shardings_for(axes, specs, mesh, sharding.make_rules())
    assert isinstance(sh, kvc.PagedKVCache)
    assert sh.k.spec == P(None, None, None, "model")
    assert sh.k_scale.spec == P(None, None, None, "model")
    assert sh.page_table.spec == P()
    assert sh.lens.spec == P()


# ---------------------------------------------------------------------------
# no fp logical-cache materialization on the fused paged path
# ---------------------------------------------------------------------------

def test_paged_decode_kv8_has_no_logical_cache_materialization(micro):
    """The fused paged path never gathers the page table into a logical
    (B, S, Hkv, D) fp cache — pages stream tile-by-tile. The XLA fallback
    jaxpr is the positive control (it DOES gather)."""
    cfg, _, params = micro
    qcfg = QuantConfig(w_bits=4, a_bits=8, group_size=32, lwc=False,
                       kv_bits=8)
    packed = quantize_lm_packed(params, cfg, qcfg)
    d = cfg.resolved_head_dim
    b, ps, mpps = 2, 8, 3
    tok = jnp.zeros((b, 1), jnp.int32)

    def jaxpr_for(mode):
        qm = QuantizedModel(cfg, qcfg, kernel_mode=mode)
        store = kvc.PagedCache(qm, max_batch=b, max_len=ps * mpps,
                               page_size=ps)
        for slot in range(b):
            store.reserve(slot, 7)
        cache = dataclasses.replace(
            store.cache, lens=jnp.full((b,), 7, jnp.int32))
        return jax.make_jaxpr(qm.decode_step)(packed, tok, cache).jaxpr

    s_log = ps * mpps
    fused = kc.fp_cache_avals(jaxpr_for("interpret"), s_log,
                                    cfg.num_kv_heads, d)
    assert not fused, f"logical-cache fp intermediates on fused path: {fused}"
    control = kc.fp_cache_avals(jaxpr_for("auto"), s_log,
                                      cfg.num_kv_heads, d)
    assert control, "positive control lost: fallback no longer gathers"
