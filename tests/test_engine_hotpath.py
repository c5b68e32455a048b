"""Compile-once serving hot path: padded-bucket prefill identity, fused
lax.scan decode bit-identity, jitted-executable cache behavior, continuous
batching (slot pool + segmented join/leave) identity, and batched DPU
preprocessing wiring."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.configs import reduced
from repro.core.batching import analytical_knee, derive_policy
from repro.core.batching.buckets import Batch, Request
from repro.models import lm
from repro.serving.engine import EngineConfig, ServingEngine, build_engine


@pytest.fixture(scope="module")
def tiny():
    cfg = reduced("tinyllama-1.1b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    return cfg, params


def _ragged_prompts(cfg, lens, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, cfg.vocab, n).astype(np.int32) for n in lens]


def _assert_logits_match(got, ref):
    """Padding must not change a request's result: the greedy token is
    exact, and the logits agree to within 4 ulps of the row's largest
    logit (XLA may order a reduction differently for a different batch
    shape, which moves the last bit; no semantic change moves only that)."""
    got, ref = np.asarray(got), np.asarray(ref)
    assert int(np.argmax(got)) == int(np.argmax(ref))
    tol = 4 * np.spacing(np.abs(ref).max().astype(ref.dtype))
    np.testing.assert_allclose(got, ref, rtol=0, atol=tol)


def _left_pad(prompts, lp, bp):
    toks = np.zeros((bp, lp), np.int32)
    off = np.full(bp, lp, np.int32)
    for i, p in enumerate(prompts):
        toks[i, lp - len(p):] = p
        off[i] = lp - len(p)
    return jnp.asarray(toks), jnp.asarray(off)


def test_padded_prefill_matches_unpadded(tiny):
    """Left-padding to a (batch, len) bucket with pos_offset masking must not
    change any request's last-token logits vs running it alone unpadded
    (greedy token exact, logits within a few ulps)."""
    cfg, params = tiny
    steps, lp = 4, 16
    prompts = _ragged_prompts(cfg, [5, 12, 9])
    refs = [
        np.asarray(lm.prefill(params, jnp.asarray(p)[None], cfg,
                              cache_len=len(p) + steps)[0][0, 0])
        for p in prompts
    ]
    toks, off = _left_pad(prompts, lp, 4)  # batch-padded 3 -> 4 rows
    logits, _ = lm.prefill(params, toks, cfg, pos_offset=off, cache_len=lp + steps)
    for i, ref in enumerate(refs):
        _assert_logits_match(logits[i, 0], ref)


def test_padded_decode_tokens_match_unpadded(tiny):
    """Greedy continuation of a padded ragged batch equals per-row unpadded
    prefill+decode token-for-token."""
    cfg, params = tiny
    steps, lp = 4, 16
    prompts = _ragged_prompts(cfg, [5, 12, 9], seed=3)
    refs = []
    for p in prompts:
        logits, cache = lm.prefill(params, jnp.asarray(p)[None], cfg,
                                   cache_len=len(p) + steps)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs = [tok]
        for t in range(steps - 1):
            logits, cache = lm.decode(params, cache, tok, jnp.int32(len(p) + t), cfg)
            tok = jnp.argmax(logits, -1).astype(jnp.int32)
            outs.append(tok)
        refs.append(np.concatenate([np.asarray(o[0]) for o in outs]))

    toks, off = _left_pad(prompts, lp, 4)
    logits, cache = lm.prefill(params, toks, cfg, pos_offset=off, cache_len=lp + steps)
    gen, _ = lm.generate(params, cache, logits, lp, cfg, steps=steps, pos_offset=off)
    gen = np.asarray(gen)
    for i, ref in enumerate(refs):
        np.testing.assert_array_equal(gen[i], ref)


def test_generate_bit_identical_to_decode_loop(tiny):
    """lm.generate (fused lax.scan) == argmax + sequential lm.decode loop,
    bit-for-bit, on the same padded inputs."""
    cfg, params = tiny
    steps, lp = 6, 16
    prompts = _ragged_prompts(cfg, [7, 15, 3, 10], seed=11)
    toks, off = _left_pad(prompts, lp, 4)

    logits, cache = lm.prefill(params, toks, cfg, pos_offset=off, cache_len=lp + steps)
    gen, _ = lm.generate(params, cache, logits, lp, cfg, steps=steps, pos_offset=off)

    logits, cache = lm.prefill(params, toks, cfg, pos_offset=off, cache_len=lp + steps)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [tok]
    for t in range(steps - 1):
        logits, cache = lm.decode(params, cache, tok, jnp.int32(lp + t), cfg,
                                  pos_offset=off)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(tok)
    loop = np.concatenate([np.asarray(o) for o in outs], axis=1)
    np.testing.assert_array_equal(np.asarray(gen), loop)


def test_padded_prefill_matches_unpadded_ssm_trained_biases():
    """Mamba2 with nonzero conv/dt biases (as in any trained checkpoint):
    left-pad slots must stay state-neutral — the conv bias would otherwise
    leak nonzero activations into the SSM state across the pad region."""
    cfg = reduced("mamba2-370m")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    key = jax.random.PRNGKey(42)

    def perturb(tree):
        out = {}
        for k, v in tree.items():
            if isinstance(v, dict):
                out[k] = perturb(v)
            elif k in ("conv_b", "dt_bias"):
                out[k] = v + 0.3 * jax.random.normal(
                    jax.random.fold_in(key, hash(k) % 997), v.shape, v.dtype
                )
            else:
                out[k] = v
        return out

    params = perturb(params)
    steps, lp = 3, 16
    prompts = _ragged_prompts(cfg, [6, 11], seed=5)
    refs = [
        np.asarray(lm.prefill(params, jnp.asarray(p)[None], cfg,
                              cache_len=len(p) + steps)[0][0, 0])
        for p in prompts
    ]
    toks, off = _left_pad(prompts, lp, 2)
    logits, _ = lm.prefill(params, toks, cfg, pos_offset=off, cache_len=lp + steps)
    for i, ref in enumerate(refs):
        _assert_logits_match(logits[i, 0], ref)


def test_pos_offset_rejected_with_image_prefix():
    """Left-pad bucketing would zero the leading img_embeds slots; the
    combination must fail loudly, not corrupt silently."""
    cfg = reduced("phi-3-vision-4.2b")
    params = lm.init_params(cfg, jax.random.PRNGKey(0), dtype=cfg.dtype)
    toks = jnp.zeros((1, max(cfg.n_img_tokens + 4, 8)), jnp.int32)
    img = jnp.zeros((1, cfg.n_img_tokens, cfg.d_model), jnp.dtype(cfg.dtype))
    with pytest.raises(ValueError, match="pos_offset"):
        lm.prefill(params, toks, cfg, img_embeds=img,
                   pos_offset=jnp.zeros((1,), jnp.int32) + 2)


def _mk_batch(lens, rid0=0):
    reqs = [
        Request(rid=rid0 + i, arrival=0.0, length=float(n))
        for i, n in enumerate(lens)
    ]
    return Batch(requests=reqs, bucket_id=0, formed_at=0.0)


def test_engine_compiles_once_per_bucket(tiny):
    """Repeated ragged batches in the same (batch, len) shape bucket trigger
    exactly one prefill compilation + one generate compilation; every later
    batch is a cache hit and traces nothing."""
    cfg, params = tiny
    engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=4))
    for w in range(4):
        engine._execute(_mk_batch([17 + w, 25, 30 - w, 21], rid0=10 * w))
    assert engine.stats["prefill_compiles"] == 1
    assert engine.stats["prefill_traces"] == 1
    assert engine.stats["generate_traces"] == 1
    assert engine.stats["decode_step_traces"] == 0
    assert engine.stats["prefill_cache_hits"] == 3
    # a new bucket compiles exactly once more
    engine._execute(_mk_batch([40, 50, 60, 33], rid0=100))
    assert engine.stats["prefill_compiles"] == 2
    assert engine.stats["prefill_traces"] == 2
    assert engine.stats["generate_traces"] == 2


def test_engine_bucket_shape_pow2(tiny):
    cfg, params = tiny
    engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=2))
    assert engine.bucket_shape(3, 17) == (4, 32)
    assert engine.bucket_shape(8, 32) == (8, 32)
    assert engine.bucket_shape(1, 1) == (1, 8)


def test_run_until_idle_uses_real_flush_deadline(tiny):
    """Timeout flushes advance to BucketedBatcher.next_deadline(): formed_at
    must equal oldest_ready + time_queue, not a fabricated poll time."""
    cfg, params = tiny
    engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=2))
    reqs = [Request(rid=i, arrival=0.0, length=12.0) for i in range(2)]
    for r in reqs:
        engine.submit(r)  # far below batch_max -> flush happens on timeout
    deadline = engine.batcher.next_deadline()
    assert deadline is not None
    done = engine.run_until_idle()
    assert len(done) == 2
    assert all(r.payload is not None and len(r.payload) == 2 for r in done)


# ---------------------------------------------------------------------------
# Continuous batching: slot pool + segmented decode with in-flight join/leave
# ---------------------------------------------------------------------------


def _isolated_ref(cfg, params, rid, n, steps):
    """Reference: the request decoded alone via lm.prefill + sequential
    lm.decode (no padding, no pool, no segments)."""
    prompt = np.random.default_rng(rid).integers(0, cfg.vocab, n).astype(np.int32)
    logits, cache = lm.prefill(params, jnp.asarray(prompt)[None], cfg,
                               cache_len=n + steps)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [np.asarray(tok[0])]
    for t in range(steps - 1):
        logits, cache = lm.decode(params, cache, tok, jnp.int32(n + t), cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(tok[0]))
    return np.concatenate(outs)


def _cheap_policy():
    return derive_policy({0: analytical_knee(1_000_000, chips=1)},
                         n_slices=1, bucket_width=64.0)


def test_continuous_join_leave_bit_identical(tiny):
    """The masking/pos_offset proof: a request decoded via segmented
    join/leave in the slot pool is bit-identical to the same request decoded
    alone via lm.decode — including requests that JOIN while another is
    mid-flight and LEAVE (retire) while others keep decoding."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=12, max_prompt_len=32)
    engine = build_engine(cfg, ec=ec)
    r1 = Request(rid=1, arrival=0.0, length=9.0, max_new_tokens=12)
    r2 = Request(rid=2, arrival=0.0, length=23.0, max_new_tokens=5)
    r3 = Request(rid=3, arrival=0.0, length=14.0, max_new_tokens=9)
    engine._admit([r1])
    engine._decode_segment(4)          # r1 decodes alone
    engine._admit([r2, r3])            # join while r1 is mid-flight
    for _ in range(3):
        engine._decode_segment(4)      # r2 leaves first, then r3, then r1
    done = {r.rid: r for r in engine.completed}
    assert set(done) == {1, 2, 3}
    for r in done.values():
        assert len(r.payload) == r.max_new_tokens
        ref = _isolated_ref(cfg, engine.params, r.rid, int(r.length),
                            len(r.payload))
        np.testing.assert_array_equal(r.payload, ref)


def test_continuous_run_until_idle_matches_isolated(tiny):
    """End-to-end: heterogeneous budgets through submit/run_until_idle, with
    more requests than slots (slot reuse), stay bit-identical to isolated
    decode and honor per-request budgets."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=12, max_prompt_len=32)
    engine = build_engine(cfg, ec=ec)
    spec = [(9, 12), (23, 5), (14, 8), (17, 12), (11, 3), (20, 7)]
    for i, (n, b) in enumerate(spec):
        engine.submit(Request(rid=i, arrival=0.0, length=float(n),
                              max_new_tokens=b))
    done = engine.run_until_idle()
    assert len(done) == len(spec)
    for r in done:
        assert len(r.payload) == r.max_new_tokens
        ref = _isolated_ref(cfg, engine.params, r.rid, int(r.length),
                            len(r.payload))
        np.testing.assert_array_equal(r.payload, ref)


def test_continuous_join_leave_bit_identical_ssm():
    """Slot-pool admission also covers SSM caches (conv tail + state row
    copies): mamba2 join/leave matches isolated decode bit-for-bit."""
    cfg = reduced("mamba2-370m")
    ec = EngineConfig(continuous=True, max_slots=2, segment_len=4,
                      max_new_tokens=6, max_prompt_len=16)
    engine = build_engine(cfg, ec=ec)
    r1 = Request(rid=11, arrival=0.0, length=6.0, max_new_tokens=6)
    r2 = Request(rid=12, arrival=0.0, length=11.0, max_new_tokens=4)
    engine._admit([r1])
    engine._decode_segment(4)
    engine._admit([r2])                # joins while r1 is mid-flight
    engine._decode_segment(4)
    done = {r.rid: r for r in engine.completed}
    assert set(done) == {11, 12}
    for r in done.values():
        ref = _isolated_ref(cfg, engine.params, r.rid, int(r.length),
                            len(r.payload))
        np.testing.assert_array_equal(r.payload, ref)


def test_continuous_steady_state_traces(tiny):
    """Steady-state continuous serving traces exactly TWO programs — one
    prefill+admit bucket and one segment. Joins, leaves, slot reuse, clock
    growth across waves: none of it retraces."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=8, max_prompt_len=32)
    engine = build_engine(cfg, ec=ec)
    n = 0
    for wave in range(3):
        for i, (l, b) in enumerate([(17, 8), (25, 3), (30, 6), (21, 8), (19, 5)]):
            engine.submit(Request(rid=100 * wave + i, arrival=0.0,
                                  length=float(l), max_new_tokens=b))
            n += 1
        engine.run_until_idle()
    assert len(engine.completed) == n
    assert engine.stats["prefill_traces"] == 1
    assert engine.stats["segment_traces"] == 1
    assert engine.stats["generate_traces"] == 0
    assert engine.stats["decode_step_traces"] == 0
    assert engine.stats["admitted"] == engine.stats["retired"] == n
    assert engine.stats["segments"] > 0
    assert 0.0 < engine.mean_slot_occupancy() <= 1.0


def test_continuous_eos_retires_early(tiny):
    """A row emitting eos_id frees its slot before its budget is spent and
    its payload is truncated at the first eos."""
    cfg, params = tiny
    base = dict(continuous=True, max_slots=2, segment_len=4,
                max_new_tokens=8, max_prompt_len=32)
    e1 = build_engine(cfg, ec=EngineConfig(**base))
    e1.submit(Request(rid=7, arrival=0.0, length=12.0))
    (full,) = e1.run_until_idle()
    assert len(full.payload) == 8
    eos = int(full.payload[2])
    exp_len = int(np.flatnonzero(full.payload == eos)[0]) + 1
    e2 = build_engine(cfg, ec=EngineConfig(eos_id=eos, **base))
    e2.submit(Request(rid=7, arrival=0.0, length=12.0))
    (r,) = e2.run_until_idle()
    assert int(r.payload[-1]) == eos
    assert len(r.payload) == exp_len < 8
    np.testing.assert_array_equal(r.payload, full.payload[:exp_len])


def test_continuous_rejects_oversized_prompt_at_submit(tiny):
    """Oversized prompts must fail at submit — before they are enqueued —
    so an admission group is never lost mid-flight to a late ValueError."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=2, segment_len=4,
                      max_new_tokens=4, max_prompt_len=32)
    engine = ServingEngine(cfg, params, _cheap_policy(), ec)
    with pytest.raises(ValueError, match="max_prompt_len"):
        engine.submit(Request(rid=1, arrival=0.0, length=33.0))
    assert engine.batcher.pending() == 0  # nothing half-enqueued


def test_continuous_clock_rebase_is_bit_invariant(tiny):
    """Sustained serving rebases the clock (pos -> pos - k*ring for every
    slot) so int32 positions stay bounded; in-flight and future requests
    must be bit-unaffected. Simulate a long-lived engine by shifting the
    clock+offsets up by k*ring (the exact state a long run would reach),
    then serve across the rebase threshold."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=8, max_prompt_len=32)
    engine = build_engine(cfg, ec=ec)
    r1 = Request(rid=41, arrival=0.0, length=9.0, max_new_tokens=8)
    engine._admit([r1])
    engine._decode_segment(4)      # r1 mid-flight
    shift = 9 * engine.pool_len    # past the rebase threshold
    engine._clock += shift
    engine._pool_off += np.int32(shift)
    engine._decode_segment(4)      # triggers _rebase_clock with r1 live
    assert engine._clock < engine.ec.max_prompt_len + 8 * engine.pool_len
    r2 = Request(rid=42, arrival=0.0, length=14.0, max_new_tokens=6)
    engine._admit([r2])            # joins post-rebase
    engine._decode_segment(4)
    engine._decode_segment(4)
    done = {r.rid: r for r in engine.completed}
    assert set(done) == {41, 42}
    for r in done.values():
        ref = _isolated_ref(cfg, engine.params, r.rid, int(r.length),
                            len(r.payload))
        np.testing.assert_array_equal(r.payload, ref)


def test_continuous_admission_near_ring_wrap_is_bit_identical(tiny):
    """Regression (found by the PR 4 preprocess-overlap bench): a request
    admitted when the slot-pool clock sits at/near a multiple of the ring
    length must decode the SAME tokens as one admitted at the initial
    clock. Under the old shared-clock ring placement the KV layout rotated
    with the admission clock, XLA's blocked reductions paired softmax/PV
    summands differently once the row's window wrapped the ring boundary,
    and an argmax occasionally flipped mid-sequence. The cache is now
    TRUE-POSITION indexed per row (lm._attn_decode), making the layout —
    and therefore every output bit — independent of when a request joins."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=12, max_prompt_len=32)  # pool ring 48

    def run_at(clock0):
        engine = build_engine(cfg, ec=ec)
        engine._ensure_pool()
        engine._clock = clock0
        r = Request(rid=777, arrival=0.0, length=25.0, max_new_tokens=12)
        engine._admit([r])
        while engine._slots[0] is not None:
            engine._decode_segment(4)
        return np.asarray(engine.completed[0].payload), engine.params

    base, params_ = run_at(32)
    for clock0 in (47, 48, 49, 96, 200):  # straddle ring-length multiples
        out, _ = run_at(clock0)
        np.testing.assert_array_equal(out, base)
    # and at this pool size the canonical layout matches isolated decode
    ref = _isolated_ref(cfg, params_, 777, 25, 12)
    np.testing.assert_array_equal(base, ref)


def test_engine_config_default_not_shared(tiny):
    """Regression: engines built without an explicit EngineConfig must not
    share one default instance (mutating one engine's config leaked into
    every other engine)."""
    cfg, params = tiny
    policy = _cheap_policy()
    e1 = ServingEngine(cfg, params, policy)
    e1.ec.max_new_tokens = 99
    e2 = ServingEngine(cfg, params, policy)
    assert e1.ec is not e2.ec
    assert e2.ec.max_new_tokens == EngineConfig().max_new_tokens


def test_engine_submit_batches_dpu_preprocess(tiny):
    """preprocess='dpu': pending requests carrying raw inputs are
    preprocessed as one DPU.process_batch pass at submit (same-shape groups
    share a CU launch), matching the per-request pipeline output."""
    from repro.data import preprocess_cpu as pp

    cfg, params = tiny
    engine = ServingEngine(cfg, params, _cheap_policy(),
                           EngineConfig(preprocess="dpu"))
    rng = np.random.default_rng(0)
    xs = [rng.standard_normal(48000).astype(np.float32) for _ in range(3)]
    xs.append(rng.standard_normal(32000).astype(np.float32))  # odd shape out
    reqs = [Request(rid=i, arrival=0.0, length=3.0, payload=x)
            for i, x in enumerate(xs)]
    engine.submit_many(reqs)
    assert engine.stats["dpu_batches"] == 1
    assert engine.dpu.processed == len(xs)
    assert engine.batcher.pending() == len(xs)
    for r, x in zip(reqs, xs):
        np.testing.assert_allclose(r.payload, pp.audio_pipeline(x),
                                   rtol=1e-4, atol=1e-4)


def _isolated_ref_tokens(cfg, params, prompt, steps):
    """Reference decode of an EXPLICIT token array (no rid-derived
    generator): prefill + sequential lm.decode, unpadded, alone."""
    prompt = np.asarray(prompt, np.int32)
    n = len(prompt)
    logits, cache = lm.prefill(params, jnp.asarray(prompt)[None], cfg,
                               cache_len=n + steps)
    tok = jnp.argmax(logits, -1).astype(jnp.int32)
    outs = [np.asarray(tok[0])]
    for t in range(steps - 1):
        logits, cache = lm.decode(params, cache, tok, jnp.int32(n + t), cfg)
        tok = jnp.argmax(logits, -1).astype(jnp.int32)
        outs.append(np.asarray(tok[0]))
    return np.concatenate(outs)


def test_real_prompt_roundtrip_through_slot_pool(tiny):
    """Real tokenized prompts end-to-end (ROADMAP open item): a request
    carrying an explicit token array through the continuous slot pool —
    join/leave, padding, ring clock and all — produces exactly the greedy
    continuation of THAT array, not of the synthetic per-rid prompt."""
    cfg, params = tiny
    ec = EngineConfig(continuous=True, max_slots=4, segment_len=4,
                      max_new_tokens=8, max_prompt_len=32)
    engine = build_engine(cfg, ec=ec)
    rng = np.random.default_rng(77)
    prompts = [rng.integers(0, cfg.vocab, n).astype(np.int32)
               for n in (9, 23, 14)]
    reqs = [Request(rid=900 + i, arrival=0.0, length=float(len(p)),
                    prompt=p, max_new_tokens=5 + i)
            for i, p in enumerate(prompts)]
    engine.submit_many(reqs)
    done = {r.rid: r for r in engine.run_until_idle()}
    assert set(done) == {900, 901, 902}
    for i, p in enumerate(prompts):
        r = done[900 + i]
        ref = _isolated_ref_tokens(cfg, engine.params, p, len(r.payload))
        np.testing.assert_array_equal(r.payload, ref)
        # and it differs from the synthetic-generator continuation (the
        # array really was used, not just accepted)
        syn = np.random.default_rng(r.rid).integers(0, cfg.vocab, len(p))
        assert not np.array_equal(p, syn)


def test_real_prompt_roundtrip_run_to_completion(tiny):
    """Same round-trip on the run-to-completion path (batched prefill +
    fused generate)."""
    cfg, params = tiny
    engine = build_engine(cfg, ec=EngineConfig(max_new_tokens=4))
    rng = np.random.default_rng(78)
    p = rng.integers(0, cfg.vocab, 13).astype(np.int32)
    reqs = [Request(rid=950, arrival=0.0, length=13.0, prompt=p),
            Request(rid=951, arrival=0.0, length=17.0)]  # synthetic neighbor
    engine._execute(Batch(requests=reqs, bucket_id=0, formed_at=0.0))
    done = {r.rid: r for r in engine.completed}
    ref = _isolated_ref_tokens(cfg, engine.params, p, 4)
    np.testing.assert_array_equal(done[950].payload, ref)


def test_prompt_length_mismatch_rejected_at_submit(tiny):
    """A token array that disagrees with Request.length must fail at the
    front door — length drives bucket choice and cache sizing."""
    cfg, params = tiny
    engine = build_engine(cfg, ec=EngineConfig(
        continuous=True, max_prompt_len=32))
    bad = Request(rid=1, arrival=0.0, length=9.0,
                  prompt=np.arange(5, dtype=np.int32))
    with pytest.raises(ValueError, match="prompt carries"):
        engine.submit(bad)
    assert engine.batcher.pending() == 0


def test_generate_requests_attaches_matching_prompts():
    """WorkloadSpec(vocab>0) text workloads carry real token arrays whose
    length matches max(1, int(length)) — the engine contract."""
    from repro.serving.requests import WorkloadSpec, generate_requests

    reqs = generate_requests(
        WorkloadSpec(modality="text", rate_qps=100.0, mean_len=20,
                     max_len=30, vocab=512, seed=3), 16)
    assert all(r.prompt is not None for r in reqs)
    for r in reqs:
        assert len(r.prompt) == max(1, int(r.length))
        assert r.prompt.dtype == np.int32
        assert 0 <= int(r.prompt.min()) and int(r.prompt.max()) < 512


def test_engine_payloads_unaffected_by_batch_composition(tiny):
    """The same request decodes to the same tokens whether it shares a padded
    batch with others or runs alone (the masking invariant, end to end)."""
    cfg, params = tiny
    ec = EngineConfig(max_new_tokens=4)
    e1 = build_engine(cfg, ec=ec)
    e1._execute(_mk_batch([9, 23, 14]))
    together = {r.rid: r.payload for r in e1.completed}
    e2 = build_engine(cfg, ec=ec)
    for i, n in enumerate([9, 23, 14]):
        e2._execute(_mk_batch([n], rid0=i))
    alone = {r.rid: r.payload for r in e2.completed}
    for rid in together:
        np.testing.assert_array_equal(together[rid], alone[rid])
