"""Prefill calls compute a row tile set by the bucket, not the whole pool.

One-pass admission and chunked prefill run `row_tile(max_slots, tokens
per row)` rows per call; a wider group runs as several calls of the same
program. Against the same engine with the tile widened to the pool (the
path where every call computes max_slots rows), tiled calls give the same
first tokens and the same pool, bit for bit: the per-row math is the same,
only the number of padding rows changes. Rows a call does not name are
never read or written, split groups add no executable, and ADMIT /
PREFILL_CHUNK say how many rows their calls computed."""
import numpy as np
import pytest

jax = pytest.importorskip("jax")
import jax.numpy as jnp  # noqa: E402

from repro.configs import reduced  # noqa: E402
from repro.core.batching import analytical_knee, derive_policy  # noqa: E402
from repro.core.batching.buckets import Request  # noqa: E402
from repro.models import lm  # noqa: E402
from repro.serving import engine as engine_mod  # noqa: E402
from repro.serving import telemetry as tm  # noqa: E402
from repro.serving.engine import (  # noqa: E402
    EngineConfig, ServingEngine, row_tile,
)

SLOTS, LP = 32, 128


@pytest.fixture(scope="module")
def models():
    out = {}
    for name in ("tinyllama-1.1b", "mamba2-370m"):
        cfg = reduced(name)
        out[name] = (cfg, lm.init_params(cfg, jax.random.PRNGKey(3),
                                         dtype=cfg.dtype))
    return out


def _policy():
    return derive_policy({0: analytical_knee(1_000_000, chips=1)},
                         n_slices=1, bucket_width=64.0)


def _engine(cfg, params, **kw):
    ec = dict(continuous=True, max_slots=SLOTS, segment_len=4,
              max_new_tokens=4, max_prompt_len=LP)
    ec.update(kw)
    return ServingEngine(cfg, params, _policy(), EngineConfig(**ec))


def _call(wide, monkeypatch, fn, *a):
    """Call into an engine; `wide` widens every prefill call it makes to
    the whole pool (the tile is derived at each call)."""
    with monkeypatch.context() as m:
        if wide:
            m.setattr(engine_mod, "PREFILL_CALL_TOKENS", 512 * SLOTS * LP)
        return fn(*a)


def _reqs(rid0, n, lens=None, budget=4):
    lens = lens or [65 + (37 * i) % 64 for i in range(n)]  # all in bucket 128
    return [Request(rid=rid0 + i, arrival=0.0, length=float(lens[i]),
                    max_new_tokens=budget) for i in range(n)]


def _same_state(a, b):
    for s, (x, y) in enumerate(zip(a._slots, b._slots)):
        assert (x is None) == (y is None), s
        if x is not None:
            assert x.req.rid == y.req.rid
            assert x.produced == y.produced, (s, x.produced, y.produced)
            assert (x.live, x.filled) == (y.live, y.filled)
    np.testing.assert_array_equal(a._tok, b._tok)
    np.testing.assert_array_equal(a._pool_off, b._pool_off)
    for p, q in zip(jax.tree.leaves(a._pool), jax.tree.leaves(b._pool)):
        np.testing.assert_array_equal(np.asarray(p), np.asarray(q))


def test_row_tile_is_derived_from_tokens_per_row():
    # the widths of the chip cells: 96 slots at one-pass buckets 8..128,
    # 16 slots at bucket / chunk 256
    assert [row_tile(96, lp) for lp in (8, 16, 32, 64, 128)] == \
        [64, 32, 16, 8, 4]
    assert row_tile(16, 256) == 2
    # small pools keep the pool's width
    assert row_tile(4, 8) == row_tile(4, 128) == 4
    assert row_tile(SLOTS, LP) == 4
    assert row_tile(8, 3) == 8           # ceil(512 / 3) = 171 -> 256 -> 8


@pytest.mark.parametrize("arch", ["tinyllama-1.1b", "mamba2-370m"])
def test_tiled_admission_matches_pool_wide_calls(models, monkeypatch, arch):
    """Groups of 1, R and 2R+1 rows admitted one-pass in calls of R rows
    give the first tokens and the pool (K/V, SSM state and conv tails) of
    a single call over all max_slots rows, bit for bit; earlier groups'
    rows stay as they were, and the split adds no executable."""
    cfg, params = models[arch]
    tiled, wide = _engine(cfg, params), _engine(cfg, params)
    r = row_tile(SLOTS, LP)
    assert r == 4
    rid = 100
    for n in (1, r, 2 * r + 1):
        for eng in (tiled, wide):
            _call(eng is wide, monkeypatch, eng._admit, _reqs(rid, n))
        _same_state(tiled, wide)
        rid += 100
    assert tiled.stats["prefill_compiles"] == 1
    assert tiled.stats["prefill_traces"] == 1
    # rows_computed: R x calls per admission; the wide engine pays the pool
    adm = tiled.tracer.of(tm.ADMIT)
    assert [e.extra["rows"] for e in adm] == [1, r, 2 * r + 1]
    assert [e.extra["rows_computed"] for e in adm] == [r, r, 3 * r]
    assert tiled.stats["prefill_rows_computed"] == 5 * r
    assert [e.extra["rows_computed"] for e in wide.tracer.of(tm.ADMIT)] == \
        [SLOTS] * 3


def test_tiled_chunks_match_pool_wide_calls(models, monkeypatch):
    """Chunked prefill with a class's rows spread over several calls —
    two admissions of one class at different columns, and a prefix-hit
    row resuming at its column — leaves the same pool and first tokens as
    calls over all max_slots rows, after every step; rows_computed on each
    PREFILL_CHUNK is R x calls, and one class is one executable."""
    cfg, params = models["tinyllama-1.1b"]
    kw = dict(chunk_lens=(64,), prefix_cache_bytes=1 << 26)
    tiled, wide = _engine(cfg, params, **kw), _engine(cfg, params, **kw)
    r = row_tile(SLOTS, 64)
    assert r == 8
    donor = np.random.default_rng(7).integers(0, cfg.vocab, 100)
    hit = np.concatenate([donor[:70],
                          np.random.default_rng(8).integers(0, cfg.vocab, 30)])

    def step(name, *a, compare=True):
        for eng in (tiled, wide):
            _call(eng is wide, monkeypatch, getattr(eng, name), *a)
        if compare:
            _same_state(tiled, wide)

    # the donor retires at its first token and leaves its first 64 prompt
    # tokens' K/V in the store
    d = Request(rid=1, arrival=0.0, length=100.0, prompt=donor,
                max_new_tokens=1)
    step("_begin_chunked", [d], LP, 64, None, compare=False)
    while tiled._chunk_q:
        step("_advance_chunks", None)
    assert tiled.stats["prefix_inserts"] == wide.stats["prefix_inserts"] == 1
    # step 1: nine cold rows at column 0, the hit row at column 64
    h = Request(rid=2, arrival=0.0, length=100.0, prompt=hit,
                max_new_tokens=4)
    step("_begin_chunked", _reqs(10, 9) + [h], LP, 64, None)
    assert tiled.stats["prefix_hits"] == wide.stats["prefix_hits"] == 1
    assert sorted(q.base for q in tiled._chunk_q) == [0, 64]
    step("_advance_chunks", None)
    # step 2: a second admission joins the class: 9 rows at column 64 and
    # 8 at column 0
    step("_begin_chunked", _reqs(30, 8), LP, 64, None)
    step("_advance_chunks", None)
    # step 3: the last 8 rows finish
    step("_advance_chunks", None)
    assert not tiled._chunk_q and not wide._chunk_q
    live = [st for st in tiled._slots if st is not None]
    assert len(live) == 18 and all(st.live and st.produced for st in live)
    chunks = tiled.tracer.of(tm.PREFILL_CHUNK)
    # donor: 2 steps of 1 row; then 10, 17 and 8 rows
    assert [e.extra["rows"] for e in chunks] == [1, 1, 10, 17, 8]
    assert [e.extra["rows_computed"] for e in chunks] == \
        [r, r, 2 * r, 3 * r, r]
    assert tiled.stats["prefill_rows_computed"] == 8 * r
    assert tiled.stats["prefill_compiles"] == 1
    assert tiled.stats["prefill_traces"] == 1


def test_tiled_engine_serves_like_the_pool_wide_engine(models, monkeypatch):
    """Through the scheduler's own groups, segments and retirement, with
    more requests than one tile and mixed buckets: every payload equals
    the pool-wide engine's."""
    cfg, params = models["tinyllama-1.1b"]
    # 12 prompts in bucket 128 (chunked, 8-row tiles), 9 in bucket 64
    # (one-pass, 8-row tiles), 3 short
    lens = [120, 33, 70, 128, 65, 41, 100, 90, 50, 110, 77, 12, 66, 123, 35,
            58, 81, 64, 9, 99, 47, 116, 61, 17]
    out, split = [], []
    for wide in (False, True):
        eng = _engine(cfg, params, chunk_lens=(64,), max_new_tokens=6)
        eng.submit_many([Request(rid=700 + i, arrival=0.0, length=float(n),
                                 max_new_tokens=1 + i % 6)
                         for i, n in enumerate(lens)])
        done = _call(wide, monkeypatch, eng.run_until_idle)
        out.append({q.rid: np.asarray(q.payload) for q in done})
        split.append(max(e.extra["rows_computed"] for e in
                         eng.tracer.of(tm.ADMIT, tm.PREFILL_CHUNK)))
    assert sorted(out[0]) == sorted(out[1]) == [700 + i
                                               for i in range(len(lens))]
    for rid, p in out[0].items():
        np.testing.assert_array_equal(p, out[1][rid])
    assert split[0] > row_tile(SLOTS, 64)   # some group ran as several calls
    assert split[1] == SLOTS


def _random_pool(cfg, slots, cache_len, seed):
    pool = lm.alloc_slot_pool(cfg, slots, cache_len)
    leaves, tree = jax.tree.flatten(pool)
    keys = jax.random.split(jax.random.PRNGKey(seed), len(leaves))
    return jax.tree.unflatten(tree, [
        jax.random.normal(k, x.shape).astype(x.dtype)
        for k, x in zip(keys, leaves)])


@pytest.mark.parametrize("program", ["chunk", "admit"])
def test_rows_outside_a_call_are_untouched(models, program):
    """The programs write only the rows their slot ids name (padding rows
    name a row past the pool): every other pool row, and every column a
    chunk call does not own, is the same bit for bit."""
    cfg, params = models["tinyllama-1.1b"]
    slots, lp, c, cl = 8, 32, 16, 48
    pool = _random_pool(cfg, slots, cl, 1)
    before = [np.asarray(x) for x in jax.tree.leaves(pool)]
    sids = np.array([5, 2, slots, slots], np.int32)
    rng = np.random.default_rng(0)
    if program == "chunk":
        toks = rng.integers(0, cfg.vocab, (4, c)).astype(np.int32)
        off = np.array([3, 0, lp, lp], np.int32)
        start = np.array([0, 16, 0, 0], np.int32)
        _, new = jax.jit(lm.prefill_chunk_into_slots,
                         static_argnames=("cfg", "lp"))(
            params, jnp.asarray(toks), pool, jnp.asarray(start),
            jnp.asarray(sids), cfg=cfg, pos_offset=jnp.asarray(off), lp=lp)
    else:
        toks = rng.integers(0, cfg.vocab, (4, lp)).astype(np.int32)
        off = np.array([3, 0, lp, lp], np.int32)
        _, new = jax.jit(lm.prefill_into_slots, static_argnames=("cfg",))(
            params, jnp.asarray(toks), pool, jnp.asarray(sids), jnp.int32(lp),
            cfg=cfg, pos_offset=jnp.asarray(off))
    others = [s for s in range(slots) if s not in (5, 2)]
    for x, y in zip(before, jax.tree.leaves(new)):
        y = np.asarray(y)
        ax = x.ndim - 4                  # 1 for the stacked body's layer axis
        take = (slice(None),) * ax
        np.testing.assert_array_equal(x[take + (others,)], y[take + (others,)])
        # the named rows change only in the ring prefix [0, lp)
        np.testing.assert_array_equal(x[take + ([5, 2], slice(lp, None))],
                                      y[take + ([5, 2], slice(lp, None))])
        assert not np.array_equal(x[take + ([5, 2],)], y[take + ([5, 2],)])
