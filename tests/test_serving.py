"""Serving engine: continuous batching correctness + throughput accounting."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import common
from repro.configs import get_reduced
from repro.models.transformer import LM
from repro.serving.engine import Request, ServeEngine


@pytest.fixture(scope="module")
def yi():
    common.set_compute_dtype(jnp.float32)  # exactness for scheduling tests
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    yield cfg, lm, params
    common.set_compute_dtype(jnp.bfloat16)


def test_engine_serves_all_requests(yi):
    cfg, lm, params = yi
    eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    rng = np.random.default_rng(0)
    for i in range(5):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32), max_new=4 + i))
    done = eng.run()
    assert sorted(r.rid for r in done) == list(range(5))
    assert all(len(r.out) == r.max_new for r in done)


def test_continuous_batching_is_isolation_safe(yi):
    """A request's output must not depend on co-scheduled requests or on
    which slot/step it was admitted in."""
    cfg, lm, params = yi
    rng = np.random.default_rng(1)
    p = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)

    e1 = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    e1.submit(Request(rid=0, prompt=p, max_new=6))
    alone = e1.run()[0].out

    e2 = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    e2.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32), max_new=3))
    e2.submit(Request(rid=1, prompt=p, max_new=6))
    batched = {r.rid: r.out for r in e2.run()}[1]
    assert batched == alone


def test_decode_matches_prefill_extension(yi):
    """Greedy decode token-by-token equals argmax over a full forward."""
    cfg, lm, params = yi
    rng = np.random.default_rng(2)
    p = rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
    eng = ServeEngine(lm, params, slots=1, max_seq=64, prefill_len=8)
    eng.submit(Request(rid=0, prompt=p, max_new=4))
    out = eng.run()[0].out

    seq = list(p)
    ref = []
    for _ in range(4):
        logits, _, _ = lm.forward(params, jnp.asarray([seq]))
        nxt = int(jnp.argmax(logits[0, -1]))
        ref.append(nxt)
        seq.append(nxt)
    assert out == ref


def test_temperature_sampling_runs(yi):
    cfg, lm, params = yi
    eng = ServeEngine(lm, params, slots=1, max_seq=64, prefill_len=8,
                      temperature=1.0, seed=7)
    eng.submit(Request(rid=0, prompt=np.arange(8, dtype=np.int32),
                       max_new=6))
    done = eng.run()
    assert len(done[0].out) == 6


def test_temperature_sampling_is_seed_deterministic(yi):
    """The temperature path of ``_sample`` must be a pure function of the
    engine seed: two engines with the same seed produce identical token
    streams, a different seed diverges. This is what makes quantized-vs-
    bf16 serving comparisons reproducible — sampling noise never masks
    (or fakes) a quantization difference."""
    cfg, lm, params = yi

    def serve(seed):
        eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8,
                          temperature=0.8, seed=seed)
        rng = np.random.default_rng(3)
        for i in range(3):
            eng.submit(Request(rid=i, prompt=rng.integers(
                0, cfg.vocab_size, size=8).astype(np.int32), max_new=5))
        return {r.rid: tuple(r.out) for r in eng.run()}

    a, b = serve(seed=11), serve(seed=11)
    assert a == b  # same seed, same schedule -> bitwise-same streams
    c = serve(seed=12)
    assert c != a  # the seed actually reaches the sampler


def test_batched_admit_matches_full_forward_reference(yi):
    """Admission is now ONE fixed-shape prefill call per engine step (the
    seed engine ran a full slots x prefill_len forward per request and
    discarded all but one slot's rows). Output token ids must be exactly
    what the seed semantics produce: prompt truncated to the *tail*
    prefill_len tokens, left-padded with zeros, then a greedy argmax
    chain — verified against a per-request full forward."""
    cfg, lm, params = yi
    rng = np.random.default_rng(7)
    # mixed lengths: shorter than, equal to, and longer than prefill_len
    prompts = [rng.integers(0, cfg.vocab_size, size=n).astype(np.int32)
               for n in (5, 8, 8, 11, 3)]
    eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    got = {r.rid: r.out for r in eng.run()}

    for i, p in enumerate(prompts):
        tail = list(p[-8:])
        seq = [0] * (8 - len(tail)) + tail
        ref = []
        for _ in range(4):
            logits, _, _ = lm.forward(params, jnp.asarray([seq]))
            nxt = int(jnp.argmax(logits[0, -1]))
            ref.append(nxt)
            seq.append(nxt)
        assert got[i] == ref, i


def test_chunked_prefill_matches_full(yi):
    """prefill_chunk splits prompts into fixed-shape pieces (bounded
    TTFT); the served token streams must be identical to full-prompt
    prefill, including for requests admitted mid-flight into reused
    slots while other slots keep decoding."""
    cfg, lm, params = yi
    rng = np.random.default_rng(8)
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(5)]

    def serve(chunk):
        eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8,
                          prefill_chunk=chunk)
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=4 + i))
        return {r.rid: r.out for r in eng.run()}

    full = serve(None)
    assert serve(4) == full
    assert serve(2) == full


def test_chunk_must_divide_prefill_len(yi):
    cfg, lm, params = yi
    with pytest.raises(ValueError, match="multiple"):
        ServeEngine(lm, params, slots=1, max_seq=64, prefill_len=8,
                    prefill_chunk=3)


def test_long_prompt_truncation_recorded_and_strict_raises(yi):
    """A prompt longer than prefill_len keeps the seed behavior (tail
    kept, silently) but is now *recorded* on the request; a strict
    engine refuses it loudly."""
    cfg, lm, params = yi
    eng = ServeEngine(lm, params, slots=1, max_seq=64, prefill_len=8)
    long_req = Request(rid=0, prompt=np.arange(12, dtype=np.int32),
                       max_new=2)
    short_req = Request(rid=1, prompt=np.arange(8, dtype=np.int32),
                        max_new=2)
    eng.submit(long_req)
    eng.submit(short_req)
    assert long_req.truncated and not short_req.truncated
    done = eng.run()
    assert sorted(r.rid for r in done) == [0, 1]

    strict = ServeEngine(lm, params, slots=1, max_seq=64, prefill_len=8,
                         strict=True)
    with pytest.raises(ValueError, match="strict"):
        strict.submit(Request(rid=2, prompt=np.arange(9, dtype=np.int32),
                              max_new=2))
    strict.submit(Request(rid=3, prompt=np.arange(8, dtype=np.int32),
                          max_new=2))  # exactly prefill_len is fine
    assert len(strict.run()) == 1


def test_zero_recompiles_after_warmup(yi):
    """Every device step is fixed-shape: after the first prefill+decode
    compile the jit caches must not grow, no matter how admissions and
    completions interleave."""
    cfg, lm, params = yi
    eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8,
                      prefill_chunk=4)
    rng = np.random.default_rng(9)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32), max_new=3))
    eng.run()
    warm = eng.compiled_cache_sizes()
    if warm["prefill"] < 0:
        pytest.skip("jit cache size introspection unavailable")
    assert warm == {"prefill": 1, "decode": 1}
    for i in range(1, 6):
        eng.submit(Request(rid=i, prompt=rng.integers(
            0, cfg.vocab_size, size=8).astype(np.int32), max_new=2 + i))
    eng.run()
    assert eng.compiled_cache_sizes() == warm


def test_chunked_prefill_rejects_stateful_mixers(yi):
    """mode="chunk" needs the attention cache-offset path; ssm/rwkv
    engines must refuse chunking loudly instead of mis-serving."""
    from repro.configs import get_reduced as _gr

    cfg = _gr("rwkv6-3b")
    lm = LM(cfg)
    with pytest.raises(NotImplementedError, match="attention"):
        ServeEngine(lm, jax.eval_shape(
            lambda: lm.init(jax.random.PRNGKey(0))),
            slots=1, max_seq=64, prefill_len=8, prefill_chunk=4)


def test_autotune_blocks_warmup_covers_sparse_shapes(yi, monkeypatch):
    """autotune_blocks=True must request a sweep for every compressed GEMM
    shape at both the decode (M=slots) and prefill (M=slots*prefill_len)
    row counts — pins the NMWeight-tree walk and the Kc -> K math."""
    import dataclasses

    from repro.configs.base import SparsityConfig
    from repro.core.nmweight import NMWeight
    from repro.core.sparsity import NMConfig
    from repro.kernels import autotune

    cfg, _, _ = yi
    scfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(
            nm=NMConfig(2, 4), mode="compressed", use_kernel=True))
    lm = LM(scfg)
    params = lm.init(jax.random.PRNGKey(0))

    asked = []
    monkeypatch.setattr(
        autotune, "ensure_tuned",
        lambda m, n, k, nm, dtype=None, family="", backend="tpu":
            asked.append((m, n, k, family)) or (8, 128, 128))
    ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8,
                autotune_blocks=True)

    want = set()
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, NMWeight)):
        if isinstance(leaf, NMWeight):
            kc, n = leaf.vals.shape[-2:]
            k = kc * leaf.nm.m // leaf.nm.n
            # M = slots rows route to the decode family (its own autotune
            # keys); prefill rows sweep the default family
            want.add((2, n, k, "decode"))
            want.add((16, n, k, ""))
    assert want, "reduced config produced no compressed linears"
    assert set(asked) == want


def test_decode_step_dispatches_zero_reference_paths(yi):
    """Acceptance: with use_kernel=True, every GEMM a decode step issues
    routes to a Pallas decode-family kernel — the per-family dispatch
    counters show decode-family dispatches and zero reference-route
    entries (no record-list sniffing: the bounded history can evict,
    the counters cannot)."""
    import dataclasses

    from repro.configs.base import SparsityConfig
    from repro.core.sparsity import NMConfig
    from repro.kernels import registry

    cfg, _, _ = yi
    scfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(
            nm=NMConfig(2, 4), mode="compressed", use_kernel=True))
    lm = LM(scfg)
    params = lm.init(jax.random.PRNGKey(0))
    eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    rng = np.random.default_rng(3)
    eng.submit(Request(rid=0, prompt=rng.integers(
        0, cfg.vocab_size, size=8).astype(np.int32), max_new=4))
    registry.clear_history()
    # one step compiles prefill AND the first decode; dispatch counts at
    # trace time, so the decode compile's GEMMs are the M == slots rows
    # (only they route to the nm_matmul_decode* families)
    eng.step()
    counts = registry.dispatch_counts("nm_matmul_decode")
    assert counts and sum(counts.values()) > 0, \
        "decode compile issued no decode-family GEMMs"
    reference = {k: v for k, v in counts.items()
                 if not k[1].startswith("pallas")}
    assert not reference, reference


def test_prefill_logits_match_full_forward_and_leave_state(yi):
    """``prefill_logits`` is the prefill step's own model path on fresh
    caches: the full forward's last-position logits, with the engine's
    caches and queue untouched."""
    from repro.serving.engine import make_serve_steps

    cfg, lm, params = yi
    eng = ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8)
    toks = np.random.default_rng(5).integers(
        0, cfg.vocab_size, size=(2, 8)).astype(np.int32)
    before = jax.tree.map(np.asarray, eng.caches)
    got = eng.prefill_logits(toks)
    prefill, _ = make_serve_steps(lm)
    want, _ = prefill(params, jnp.asarray(toks), lm.init_cache(2, 64))
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-5, atol=1e-5)
    jax.tree.map(np.testing.assert_array_equal, before,
                 jax.tree.map(np.asarray, eng.caches))
    assert not eng.queue and eng.compiled_cache_sizes() == {
        "prefill": 0, "decode": 0}


def test_autotune_warmup_uses_each_weights_own_ratio(yi, monkeypatch):
    """A model mixing N:M ratios per target (2:4 ffn, 1:4 attn) must tune
    each compressed GEMM at the K its own NMConfig implies — the old
    shape-only walk assumed one global ratio and got 1:4 layers wrong."""
    import dataclasses

    from repro.configs.base import SparsityConfig
    from repro.core.nmweight import NMWeight
    from repro.core.sparsity import NMConfig
    from repro.kernels import autotune

    cfg, _, _ = yi
    scfg = dataclasses.replace(
        cfg, sparsity=SparsityConfig(
            nm=NMConfig(2, 4), mode="compressed", use_kernel=True,
            targets=("ffn", "attn_proj"),
            nm_overrides=(("attn_proj", NMConfig(1, 4)),)))
    lm = LM(scfg)
    params = lm.init(jax.random.PRNGKey(0))
    tags = {l.nm.tag for l in jax.tree.leaves(
        params, is_leaf=lambda x: isinstance(x, NMWeight))
        if isinstance(l, NMWeight)}
    assert tags == {"2:4", "1:4"}

    asked = []
    monkeypatch.setattr(
        autotune, "ensure_tuned",
        lambda m, n, k, nm, dtype=None, family="", backend="tpu":
            asked.append((m, n, k, nm.tag)) or (8, 128, 128))
    ServeEngine(lm, params, slots=2, max_seq=64, prefill_len=8,
                autotune_blocks=True)

    want = set()
    for leaf in jax.tree.leaves(
            params, is_leaf=lambda x: isinstance(x, NMWeight)):
        if isinstance(leaf, NMWeight):
            kc, n = leaf.vals.shape[-2:]
            k = kc * leaf.nm.m // leaf.nm.n
            for m_rows in (2, 16):
                want.add((m_rows, n, k, leaf.nm.tag))
    assert set(asked) == want
    # every 1:4 weight was tuned at K = 4 * Kc, not the 2:4 ratio's 2 * Kc
    assert any(tag == "1:4" for *_, tag in asked)


# ---------------------------------------------------------------------------
# block-sparse masked serving: token parity, dispatch proof, recompiles
# ---------------------------------------------------------------------------


def _mask_variant(cfg, **fields):
    """cfg with every AttnConfig mixer's mask/window fields replaced."""
    import dataclasses

    from repro.configs.base import AttnConfig

    def blk(b):
        if isinstance(b.mixer, AttnConfig):
            return dataclasses.replace(
                b, mixer=dataclasses.replace(b.mixer, **fields))
        return b

    plan = tuple(
        ((tuple(blk(x) for x in e) if isinstance(e, tuple) else blk(e)), r)
        for e, r in cfg.plan)
    return dataclasses.replace(cfg, plan=plan)


def _serve_prompts(lm, params, prompts, **kw):
    eng = ServeEngine(lm, params, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(rid=i, prompt=p, max_new=3 + i))
    return {r.rid: tuple(r.out) for r in eng.run()}, eng


def test_blocksparse_serving_token_parity_and_dispatch(yi):
    """A model carrying a local MaskSpec serves token-identically to the
    dense model carrying the equivalent sliding window (slot engine,
    full prefill — the shape that routes the bs_attention prefill
    family), with zero steady-state recompiles and trace-level proof the
    sparse lowering ran: the prefill family dispatched
    xla_bs_attention and never the dense masked_reference fallback."""
    from repro.kernels import registry
    from repro.kernels.blocksparse_attn.mask import MaskSpec

    cfg, _, params = yi  # mask/window change no param shapes
    lm_dense = LM(_mask_variant(cfg, mask=None, window=12))
    lm_mask = LM(_mask_variant(
        cfg, mask=MaskSpec("local", block=8, window=12), window=None))
    rng = np.random.default_rng(11)
    prompts = [rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
               for _ in range(4)]
    kw = dict(slots=2, max_seq=64, prefill_len=32)

    dense, _ = _serve_prompts(lm_dense, params, prompts, **kw)
    registry.clear_history()
    masked, em = _serve_prompts(lm_mask, params, prompts, **kw)
    assert masked == dense
    counts = registry.dispatch_counts("bs_attention")
    assert any(op == "bs_attention" and impl == "xla_bs_attention" and n
               for (op, impl, _), n in counts.items()), counts
    assert not any(op == "bs_attention" and impl == "masked_reference" and n
                   for (op, impl, _), n in counts.items()), counts
    assert sum(registry.dispatch_counts("bs_attention_decode").values()) > 0
    warm = em.compiled_cache_sizes()
    if warm["prefill"] >= 0:
        assert warm == {"prefill": 1, "decode": 1}
    # chunked prefill routes the decode family instead (mode="chunk");
    # tokens must not change
    chunked, _ = _serve_prompts(lm_mask, params, prompts,
                                prefill_chunk=16, **kw)
    assert chunked == dense


def test_blocksparse_paged_serving_matches_slot(yi):
    """The paged engine serves a masked model token-identically to the
    slot engine (block-table gather feeding the mask-aware decode
    path), still with zero steady-state recompiles."""
    from repro.kernels import registry
    from repro.kernels.blocksparse_attn.mask import MaskSpec

    cfg, _, params = yi
    lm_mask = LM(_mask_variant(
        cfg, mask=MaskSpec("local", block=8, window=12), window=None))
    rng = np.random.default_rng(12)
    prompts = [rng.integers(1, cfg.vocab_size, size=32).astype(np.int32)
               for _ in range(4)]
    kw = dict(slots=2, max_seq=64, prefill_len=32, prefill_chunk=16)
    slot_out, _ = _serve_prompts(lm_mask, params, prompts, **kw)
    registry.clear_history()
    paged_out, ep = _serve_prompts(lm_mask, params, prompts, paged=True,
                                   **kw)
    assert paged_out == slot_out
    assert sum(registry.dispatch_counts("bs_attention_decode").values()) > 0
    cs = ep.compiled_cache_sizes()
    assert cs in ({"prefill": 1, "decode": 1},
                  {"prefill": -1, "decode": -1}), cs
