"""Paged KV pool contract (``serving/paging.py`` + ``flash_decode_paged``):

* Kernel bit-exactness: for random permutation page tables the paged
  Pallas kernel, reading one layer of a stacked lane-dense store
  ``(L, n_pages, ps // r, K, r*hd)``, is BIT-identical to the contiguous
  kernel run at ``bkv=page_size`` over that layer's gathered cache — every
  layer index, fp pools and int8 pools with per-slot (B, K) scales and a fp
  cushion block, including retired rows (pos == -1) reading only the
  scratch page. fp + cushion folds the cushion in a different order than
  the contiguous kernel, so that combination is gated against the gather
  oracle (allclose) instead.
* Allocator invariants: reservation-based admission backpressure, page
  accounting across release/re-admit, scratch page pinned forever.
* Scheduler parity: the paged pool serves a recycling trace token-for-token
  identical to the per-request static Engine, fp and int8 (per-slot scale
  pages), and re-admission into a recycled slot never copies the cushion
  block (the same two device buffers serve the engine's whole session).
* Prefix caching: a repeated prompt stem hits the content-addressed page
  registry and skips its prefill chunk token-for-token.
* tp=2 paged parity (guarded on host device count) and the explicit
  no-slot-layout / non-pageable-family rejections.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import QuantConfig, get_config, reduced
from repro.kernels import ref as R
from repro.kernels.flash_decode import (flash_decode, flash_decode_paged,
                                        pack_pages, page_rows)
from repro.models.registry import build
from repro.serving import ContinuousEngine, Engine, Request
from repro.serving.paging import PagePool

try:                    # property tests degrade to the deterministic cases
    import hypothesis
    import hypothesis.strategies as st
except ImportError:     # pragma: no cover
    hypothesis = st = None

QN = QuantConfig(mode="none")

# ---------------------------------------------------------------------------
# Kernel: paged == contiguous, bit for bit
# ---------------------------------------------------------------------------

_B, _K, _G, _HD, _SMAX, _PS, _M, _L = 4, 2, 2, 16, 64, 32, 8, 3
_P = _SMAX // _PS
_RS = np.random.RandomState(11)
_Q = jnp.asarray(_RS.randn(_B, _K * _G, _HD).astype(np.float32))
# one dense (B, Smax, K, hd) cache per layer of the stacked store
_KF = _RS.randn(_L, _B, _SMAX, _K, _HD).astype(np.float32)
_VF = _RS.randn(_L, _B, _SMAX, _K, _HD).astype(np.float32)
_KQ = _RS.randint(-127, 128, (_L, _B, _SMAX, _K, _HD)).astype(np.int8)
_VQ = _RS.randint(-127, 128, (_L, _B, _SMAX, _K, _HD)).astype(np.int8)
_KSR = jnp.asarray(_RS.rand(_B, _K).astype(np.float32) * 0.05 + 0.01)
_VSR = jnp.asarray(_RS.rand(_B, _K).astype(np.float32) * 0.05 + 0.01)
_KC = jnp.asarray(_RS.randn(_M, _K, _HD).astype(np.float32))
_VC = jnp.asarray(_RS.randn(_M, _K, _HD).astype(np.float32))


def _paginate(k, v, seed, n_extra=3):
    """Scatter dense per-layer (L, B, Smax, K, hd) rows into a random-
    permutation lane-dense page store (L, n_pages, ps // r, K, r*hd): page
    0 stays scratch (junk content — it must never influence the output),
    logical page j of row b lands on physical page table[b, j] in every
    layer."""
    rs = np.random.RandomState(seed)
    n_pages = _B * _P + 1 + n_extra
    perm = rs.permutation(np.arange(1, n_pages))[:_B * _P]
    table = perm.reshape(_B, _P).astype(np.int32)
    r = page_rows(_HD)
    shape = (_L, n_pages, _PS // r, _K, r * _HD)
    kp = rs.randn(*shape).astype(np.float32).astype(k.dtype)
    vp = rs.randn(*shape).astype(np.float32).astype(v.dtype)
    pages = lambda x: np.asarray(pack_pages(
        jnp.asarray(x).reshape(_L, _B * _P, _PS, _K, _HD)))
    kp[:, table.reshape(-1)] = pages(k)
    vp[:, table.reshape(-1)] = pages(v)
    return jnp.asarray(kp), jnp.asarray(vp), jnp.asarray(table)


def _check_paged_kernel(pos, quantized, seed=0):
    """Every layer of the stacked store against the contiguous kernel on
    that layer's dense cache, bit for bit."""
    posv = jnp.asarray(pos, jnp.int32)
    k, v = (_KQ, _VQ) if quantized else (_KF, _VF)
    kp, vp, table = _paginate(k, v, seed)
    for layer in range(_L):
        if quantized:
            out = flash_decode_paged(_Q, kp, vp, table, posv, layer,
                                     k_scale=_KSR, v_scale=_VSR, kc=_KC,
                                     vc=_VC, interpret=True)
            # same chunk size, same online-softmax fold order -> bit-exact
            ref = flash_decode(_Q, jnp.asarray(k[layer]),
                               jnp.asarray(v[layer]), posv, k_scale=_KSR,
                               v_scale=_VSR, kc=_KC, vc=_VC, bkv=_PS,
                               interpret=True)
        else:
            out = flash_decode_paged(_Q, kp, vp, table, posv, layer,
                                     interpret=True)
            ref = flash_decode(_Q, jnp.asarray(k[layer]),
                               jnp.asarray(v[layer]), posv, bkv=_PS,
                               interpret=True)
        np.testing.assert_array_equal(np.asarray(out), np.asarray(ref),
                                      err_msg=f"layer {layer}")


@pytest.mark.parametrize("quantized", [False, True], ids=["fp", "int8"])
@pytest.mark.parametrize("pos", [
    [_M, -1, _SMAX - 1, _M - 1],    # cushion boundary, retired, full
    [-1, -1, -1, 5],                # mostly-retired pool
    [0, 17, _PS - 1, _PS],          # page-edge straddle
    [3, 60, -1, 33],                # ragged mid-decode pool
])
def test_paged_kernel_bit_identical_cases(pos, quantized):
    """Deterministic cases (always run, even without hypothesis): the paged
    kernel reproduces the contiguous kernel BIT-for-bit over permuted page
    tables, at every layer of the stacked store — fp, and int8 with
    per-slot (B, K) scales + fp cushion — including fully retired rows
    whose table points at freed pages."""
    _check_paged_kernel(pos, quantized)


if hypothesis is not None:
    @hypothesis.given(
        pos=st.lists(st.integers(min_value=-1, max_value=_SMAX - 1),
                     min_size=_B, max_size=_B),
        quantized=st.booleans(),
        seed=st.integers(min_value=0, max_value=2 ** 16))
    @hypothesis.settings(max_examples=25, deadline=None)
    def test_paged_kernel_bit_identical_property(pos, quantized, seed):
        """Property form: random per-row positions x random page-table
        permutations x fp/int8 — always bit-identical to the contiguous
        kernel."""
        _check_paged_kernel(pos, quantized, seed=seed)


def test_paged_kernel_fp_cushion_matches_oracle():
    """fp pool + cushion block: the paged kernel folds the cushion after
    the pages (the contiguous kernel folds it first), so the gate is the
    gather oracle, not bit-identity."""
    kp, vp, table = _paginate(_KF, _VF, 3)
    posv = jnp.asarray([_M, -1, _SMAX - 1, 33], jnp.int32)
    for layer in range(_L):
        out = flash_decode_paged(_Q, kp, vp, table, posv, layer, kc=_KC,
                                 vc=_VC, interpret=True)
        ref = R.flash_decode_paged_ref(_Q, kp, vp, table, posv, layer,
                                       kc=_KC, vc=_VC)
        np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                                   rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# Allocator invariants (host-side, no jax)
# ---------------------------------------------------------------------------

def test_page_pool_reserve_release_accounting():
    """Admission reserves the full worst case up front (so decode can never
    exhaust mid-flight), lazy mapping draws down the reservation, release
    returns every page, and the scratch page is never handed out."""
    pool = PagePool(n_slots=2, max_seq=128, page_size=32, n_pages=6,
                    cushion_m=3)
    # need 96 positions -> pages [0, 3); prefill writes 40 -> pages [0, 2)
    scatter = pool.admit(0, prefill_end=40, need=96)
    assert scatter is not None and pool.available() == 2
    owned = set(np.asarray(pool.table[0])[np.asarray(pool.table[0]) > 0])
    assert len(owned) == 2 and 0 not in owned
    # second identical admission exceeds 5 content pages -> backpressure
    assert pool.admit(1, prefill_end=40, need=96) is None
    pool.ensure_mapped(0, 64)           # draw the reserved decode page
    assert pool.reserved == 0 and pool.available() == 2
    pool.release(0)
    assert pool.available() == 5 and not pool.table[0].any()
    assert pool.refs[0] == 1            # scratch pinned forever
    # released pages host the next admission
    assert pool.admit(1, prefill_end=40, need=96) is not None


# ---------------------------------------------------------------------------
# Scheduler: paged pool == static Engine, token for token
# ---------------------------------------------------------------------------

def _setup(arch="paper_tiny"):
    cfg = (get_config(arch) if arch == "paper_tiny"
           else reduced(get_config(arch), dtype="float32"))
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    cushion = api.extract_cushion(params, jnp.asarray([1, 2, 3], jnp.int32),
                                  None, QN)
    return api, params, cushion


@pytest.mark.parametrize("kv_dtype", [None, "int8"], ids=["fp", "int8"])
def test_paged_scheduler_matches_engine(kv_dtype):
    """A recycling trace through the paged pool (page_size 32, per-slot
    page tables, batch-free cushion) is token-for-token identical to the
    per-request static Engine — fp and int8 (whose per-slot scale leaves
    stay densely slotted next to the paged KV leaves)."""
    api, params, cushion = _setup()
    budgets = [5, 3, 6, 4, 5]
    lens = [20, 26]
    reqs = [Request(uid=i, batch=api.make_batch(jax.random.PRNGKey(100 + i),
                                                1, lens[i % 2]),
                    max_new_tokens=n)
            for i, n in enumerate(budgets)]
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, kv_dtype=kv_dtype, paged=True,
                          page_size=32)
    outs = ce.run(reqs)
    assert ce.stats.recycles >= 1, "trace must exercise page recycling"
    L, K, hd = api.cfg.n_layers, api.cfg.n_kv_heads, api.cfg.head_dim
    r = page_rows(hd)
    assert ce.cache["k"].shape == (L, ce.n_pages, 32 // r, K, r * hd), \
        "paged pool must hold flat lane-dense pages, not per-slot rows"

    eng = Engine(api, params, QN, cushion=cushion, max_seq=128,
                 kv_dtype=kv_dtype)
    for req, out in zip(reqs, outs):
        ref = eng.generate(req.batch, req.max_new_tokens).tokens[0]
        np.testing.assert_array_equal(out.tokens, ref)
    g = ce.stats
    assert g.pages_total == ce.n_pages and g.pages_free == g.pages_total - 1
    assert g.cushion_page_refs == 1     # pool's pinned ref, no live slots


@pytest.mark.parametrize("arch,kv_dtype", [
    ("olmoe-1b-7b", None), ("internvl2-26b", None),
    ("jamba-v0.1-52b", None), ("jamba-v0.1-52b", "int8"),
], ids=["moe", "vlm", "hybrid-fp", "hybrid-int8"])
def test_paged_family_matches_engine(arch, kv_dtype):
    """Every family that pages its KV serves the paged pool token-for-token
    like the static Engine: moe's and vlm's decode scans carry the store
    by layer index like the dense one, and hybrid's period scan hands its
    attention sublayer a one-layer view of the period's pages."""
    api, params, cushion = _setup(arch)
    reqs = [Request(uid=i, batch=api.make_batch(jax.random.PRNGKey(100 + i),
                                                1, 20),
                    max_new_tokens=n)
            for i, n in enumerate([5, 3, 6, 4])]
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, kv_dtype=kv_dtype, paged=True,
                          page_size=32)
    outs = ce.run(reqs)
    assert ce.stats.recycles >= 1
    eng = Engine(api, params, QN, cushion=cushion, max_seq=128,
                 kv_dtype=kv_dtype)
    for req, out in zip(reqs, outs):
        ref = eng.generate(req.batch, req.max_new_tokens).tokens[0]
        np.testing.assert_array_equal(out.tokens, ref)


def test_page_table_syncs_flat_during_pure_decode():
    """The host->device page-table mirror runs only on actual table
    mutation: across a pure-decode window inside one page (no new
    mappings, no admissions, no releases) the ``page_table_syncs`` gauge
    stays flat, and crossing a page boundary costs exactly one sync —
    not one per step. Releasing an already-empty row is not a mutation."""
    api, params, cushion = _setup()
    ce = ContinuousEngine(api, params, QN, n_slots=3, max_seq=256,
                          cushion=cushion, paged=True, page_size=64)
    ce.start()
    for uid in range(2):
        assert ce.try_admit(Request(
            uid=uid, batch=api.make_batch(jax.random.PRNGKey(uid), 1, 20),
            max_new_tokens=50))
    ce.step()           # flushes the admission mutations
    base = ce.stats.page_table_syncs
    assert base >= 1
    # positions 24.. stay inside page 0 (64 positions) for many steps
    for _ in range(10):
        ce.step()
    assert ce.stats.page_table_syncs == base, \
        "pure-decode steps inside a mapped page must not re-sync the table"
    # decode up to the page-0/page-1 boundary: exactly one more sync for
    # the window that maps the new page (both slots map it the same step)
    while int(ce._hpos.max()) < 64:
        ce.step()
    ce.step()
    assert ce.stats.page_table_syncs == base + 1, \
        "a page-boundary crossing costs one sync, not one per step"
    # releasing a never-admitted row is a no-op: no dirty, no gauge drift
    assert not ce._pool.dirty
    gauges_before = ce._pool.gauges()
    ce._pool.release(ce.n_slots - 1)        # slot 2 never held a request
    assert not ce._pool.dirty, \
        "empty-row release must not mark the table dirty"
    assert ce._pool.gauges() == gauges_before


def test_recycle_never_copies_cushion_block():
    """The refcounted cushion lives once, batch-free, outside the page
    store: admission, decode, retirement and re-admission into the recycled
    slot all serve from the SAME device buffers — no per-slot copy, no
    re-write on recycle (the dense pool re-scattered the cushion into every
    admitted row)."""
    api, params, cushion = _setup()
    ce = ContinuousEngine(api, params, QN, n_slots=1, max_seq=128,
                          cushion=cushion, paged=True, page_size=32)
    k0, v0 = ce.cushion_block["kc"], ce.cushion_block["vc"]
    mk = lambda uid: Request(
        uid=uid, batch=api.make_batch(jax.random.PRNGKey(uid), 1, 12),
        max_new_tokens=3)
    assert ce.try_admit(mk(0))
    assert ce.stats.cushion_page_refs == 2      # pool ref + live slot
    while ce.live_count:
        ce.step()
    assert ce.stats.cushion_page_refs == 1
    assert ce.try_admit(mk(1))                  # recycled slot, no copy
    ce.step()
    assert ce.cushion_block["kc"] is k0 and ce.cushion_block["vc"] is v0
    assert ce.stats.recycles >= 1


def test_prefix_cache_hit_skips_prefill_token_for_token():
    """Requests repeating a prompt stem map the donor's pages read-only
    and prefill only the tail — greedy outputs stay token-for-token
    identical to the full-prefill static Engine, and the hit/miss counters
    prove the stem pages were actually shared."""
    api, params, cushion = _setup()
    base = np.asarray(api.make_batch(jax.random.PRNGKey(3), 1, 64)["tokens"])
    reqs = []
    for i in range(4):
        t = np.array(np.asarray(
            api.make_batch(jax.random.PRNGKey(50 + i), 1, 64)["tokens"]))
        t[:, :62] = base[:, :62]        # two full 32-pages under m=3
        reqs.append(Request(uid=i, batch={"tokens": jnp.asarray(t)},
                            max_new_tokens=4))
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, paged=True, page_size=32,
                          prefix_cache=True)
    outs = ce.run(reqs)
    assert ce.stats.prefix_hits >= 1 and ce.stats.prefix_misses >= 1
    assert ce.stats.pages_shared == 0   # all released at end of trace
    eng = Engine(api, params, QN, cushion=cushion, max_seq=128)
    for req, out in zip(reqs, outs):
        ref = eng.generate(req.batch, req.max_new_tokens).tokens[0]
        np.testing.assert_array_equal(out.tokens, ref)


def test_prefix_cache_rejects_int8_pool():
    api, params, cushion = _setup()
    with pytest.raises(ValueError, match="fp pages"):
        ContinuousEngine(api, params, QN, n_slots=1, max_seq=128,
                         cushion=cushion, kv_dtype="int8", paged=True,
                         page_size=32, prefix_cache=True)


def test_paged_rejects_family_without_pageable_cache():
    """A family whose cache has no sequence-major KV leaves (pure SSM:
    recurrent state, nothing paged) gets a clear rejection, not a cryptic
    scatter failure."""
    cfg = reduced(get_config("xlstm-350m"), dtype="float32")
    api = build(cfg)
    params = api.init_params(jax.random.PRNGKey(0))
    with pytest.raises(ValueError, match="pageable"):
        ContinuousEngine(api, params, QN, n_slots=1, max_seq=128,
                         paged=True, page_size=32)


def test_paged_pool_backpressures_then_admits():
    """Page exhaustion behaves exactly like a full slot pool: try_admit
    returns False (the caller requeues), and succeeds once a retirement
    returns pages to the free list."""
    api, params, cushion = _setup()
    # 5 content pages: one admission (prompt 12 + budget 3 + m=3 -> 18
    # positions -> 3 pages of 8... use page_size 32: 1 page + 0 reserve)
    ce = ContinuousEngine(api, params, QN, n_slots=2, max_seq=128,
                          cushion=cushion, paged=True, page_size=32,
                          n_pages=2)
    mk = lambda uid: Request(
        uid=uid, batch=api.make_batch(jax.random.PRNGKey(uid), 1, 12),
        max_new_tokens=3)
    assert ce.try_admit(mk(0))
    assert not ce.try_admit(mk(1)), \
        "second admission must backpressure on the single content page"
    while ce.live_count:
        ce.step()
    assert ce.try_admit(mk(1))          # retirement returned the page


@pytest.mark.skipif(len(jax.devices()) < 2,
                    reason="needs >= 2 devices (XLA host device count)")
def test_paged_tp2_matches_unsharded():
    """tp=2 paged pool (pages sharded on the heads axis, page table and
    cushion replicated) serves the same trace token-for-token as the
    unsharded paged engine."""
    from repro.launch.mesh import make_tp_mesh
    api, params, cushion = _setup()
    reqs = [Request(uid=i, batch=api.make_batch(jax.random.PRNGKey(100 + i),
                                                1, 20),
                    max_new_tokens=4)
            for i in range(3)]
    kw = dict(n_slots=2, max_seq=128, cushion=cushion, paged=True,
              page_size=32)
    ce1 = ContinuousEngine(api, params, QN, **kw)
    ce2 = ContinuousEngine(api, params, QN, mesh=make_tp_mesh(2), **kw)
    for o1, o2 in zip(ce1.run(reqs), ce2.run(reqs)):
        np.testing.assert_array_equal(o1.tokens, o2.tokens)
