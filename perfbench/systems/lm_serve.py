"""Drives the program's serving engine through a closed backlog.

The program is used only through its normal entry points: its model
config (``repro.configs``), ``LM`` and ``ServeEngine`` (``submit``,
``step``, ``finished``). The weights are the benchmark's own, drawn from
the seed by the reference's ``make`` in the program's compressed form and
built on the device in one jitted call.

A run: set-up (weights, engine, ``warmup_steps`` engine steps, which
compile or load every step program), then the window — engine steps from
a fixed step of the fixed length sequence until the first step that
completes at or after ``--seconds`` — then the check: the served tokens
of a sample of requests against the reference, after the engine's state
is freed.
"""
from __future__ import annotations

import dataclasses
import gc
import time

import jax
import jax.numpy as jnp
import numpy as np

from harness import compare, devtrace, device, gcwatch, spans, traffic
from harness import weights, work
from harness import window as win
from harness.record import Outcome, Record, RunArgs
from harness.spec import SpecError

# program parameter path (last two dict keys) -> the reference's role
ROLES = {
    ("embed", "embedding"): "embed_tokens",
    ("final_norm", "scale"): "norm",
    ("lm_head", "w"): "lm_head",
    ("mixer", "wq"): "q_proj", ("mixer", "wk"): "k_proj",
    ("mixer", "wv"): "v_proj", ("mixer", "wo"): "o_proj",
    ("mlp", "w_gate"): "gate_proj", ("mlp", "w_up"): "up_proj",
    ("mlp", "w_down"): "down_proj",
    ("norm1", "scale"): "input_layernorm",
    ("norm2", "scale"): "post_attention_layernorm",
}


def program_model(cfg: dict):
    """The program's LM for the configuration file, checked against it."""
    from repro.configs import get_config, get_reduced
    from repro.models.transformer import LM

    get = get_config if cfg["program_preset"] == "full" else get_reduced
    mc = get(cfg["program_arch"])
    if len(mc.plan) != 1:
        raise SpecError(f"{mc.name}: expected one group of identical layers")
    blk, _ = mc.plan[0]
    att, ffn = blk.mixer, blk.mlp
    sp = mc.sparsity
    have = {
        "hidden_size": mc.d_model, "intermediate_size": ffn.d_ff,
        "num_attention_heads": att.q_heads,
        "num_key_value_heads": att.kv_heads, "head_dim": att.head_dim,
        "num_hidden_layers": mc.n_layers, "vocab_size": mc.vocab_size,
        "rope_theta": mc.rope_theta, "rms_norm_eps": mc.norm_eps,
        "tie_word_embeddings": mc.tie_embeddings,
        "hidden_act": "silu" if ffn.act == "swiglu" else ffn.act,
    }
    want = {k: cfg[k] for k in have}
    if have != want or sp is None or (sp.nm.n, sp.nm.m) != (
            cfg["sparsity"]["n"], cfg["sparsity"]["m"]) or not set(
            cfg["sparsity"]["targets"]) <= set(sp.targets):
        raise SpecError(f"the program's {mc.name} is not the configuration "
                        f"file's: program {have}, {sp}; file {want}")
    mc = dataclasses.replace(mc, sparsity=dataclasses.replace(
        sp, use_kernel=bool(cfg["sparsity"]["kernels"])))
    return LM(mc)


def param_builder(lm, cfg: dict, ref):
    """key -> the program's parameter tree, every leaf drawn from the
    seed; stacked layers are drawn one layer at a time (``lax.map``)."""
    from repro.core.nmweight import NMWeight

    def is_nm(x):
        return isinstance(x, NMWeight)

    shapes = jax.eval_shape(lambda k: lm.init(k, jnp.bfloat16),
                            jax.random.PRNGKey(0))
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes,
                                                           is_leaf=is_nm)
    specs = ref.tensors(cfg)

    def build(key):
        out = []
        for path, leaf in leaves:
            names = tuple(p.key for p in path
                          if isinstance(p, jax.tree_util.DictKey))
            role = ROLES[names[-2:]]
            per_layer = specs[role][3]
            want = leaf.vals if is_nm(leaf) else leaf

            def one(layer, role=role, dtype=want.dtype):
                return ref.make(cfg, key, role, layer, dtype)

            val = (jax.lax.map(one, jnp.arange(want.shape[0]))
                   if per_layer else one(0))
            got = val[0] if is_nm(leaf) else val
            if got.shape != want.shape or got.dtype != want.dtype:
                raise SpecError(f"{role}: drew {got.shape} {got.dtype}, the "
                                f"program holds {want.shape} {want.dtype}")
            out.append(dataclasses.replace(leaf, vals=val[0], idx=val[1])
                       if is_nm(leaf) else val)
        return jax.tree_util.tree_unflatten(treedef, out)

    return build


class Feed:
    """Closed backlog: the fixed list of lengths, served in a loop."""

    def __init__(self, eng, backlog: list, seed: int, vocab: int):
        from repro.serving.engine import Request

        self.eng, self.backlog, self.seed, self.vocab = eng, backlog, seed, vocab
        self.Request = Request
        self.all = []          # every request submitted, in order
        self.seen = 0          # finished requests already replaced

    def submit(self, count: int) -> None:
        for _ in range(count):
            serial = len(self.all)
            plen, olen = self.backlog[serial % len(self.backlog)]
            req = self.Request(rid=serial, prompt=traffic.prompt_tokens(
                self.seed, serial, plen, self.vocab), max_new=olen)
            self.eng.submit(req)
            self.all.append(req)

    def refill(self) -> None:
        done = len(self.eng.finished)
        self.submit(done - self.seen)
        self.seen = done


def _sample(reqs: list, n: int, seed: int) -> list:
    """The longest request (most served tokens) and n-1 others drawn from
    the seed, among those that served a token."""
    cand = sorted((r for r in reqs if r.out), key=lambda r: (-len(r.out), r.rid))
    if not cand:
        return []
    rest = cand[1:]
    pick = np.random.default_rng([seed, 7]).permutation(len(rest))[:n - 1]
    return [cand[0]] + [rest[i] for i in sorted(pick)]


def _check_batch(sample: list, prefill_len: int, bucket: int = 128):
    """Token batch (B, S) of each request as the engine saw it — the prompt
    left-padded with 0 to prefill_len, then its served tokens but the last —
    and the (row, position) and token of every served token."""
    seqs, rows, toks = [], [], []
    for b, r in enumerate(sample):
        p = np.asarray(r.prompt, np.int32)[-prefill_len:]
        padded = np.concatenate([np.zeros(prefill_len - len(p), np.int32), p])
        seqs.append(np.concatenate([padded, np.asarray(r.out[:-1], np.int32)]))
        for j, t in enumerate(r.out):
            rows.append((b, prefill_len - 1 + j))
            toks.append(t)
    width = -(-max(len(s) for s in seqs) // bucket) * bucket
    batch = np.zeros((len(seqs), width), np.int32)
    for b, s in enumerate(seqs):
        batch[b, :len(s)] = s
    return batch, np.asarray(rows, np.int32), np.asarray(toks, np.int32)


def run(args: RunArgs) -> Outcome:
    from repro import obs as obs_mod
    from repro.serving.engine import ServeEngine

    cell, cfg, t = args.cell, args.cell.config, args.cell.traffic
    ref = cell.reference()
    setup = {"import_s": time.perf_counter() - args.t_process0}
    obs = obs_mod.enable()  # its engine.prefill spans count the prefill calls

    t0 = time.perf_counter()  # weights_s: compile or cache load, then draw
    lm = program_model(cfg)
    key = weights.seed_key(args.seed)
    params = jax.jit(param_builder(lm, cfg, ref))(key)  # one call, on the device
    jax.block_until_ready(params)
    setup["weights_s"] = time.perf_counter() - t0

    t0 = time.perf_counter()
    eng = ServeEngine(lm, params, slots=t["slots"], max_seq=t["max_seq"],
                      prefill_len=t["prefill_len"],
                      prefill_chunk=t["prefill_chunk"], temperature=0.0,
                      strict=True)
    del params
    feed = Feed(eng, traffic.backlog(t), args.seed, cfg["vocab_size"])
    feed.submit(len(feed.backlog))
    setup["engine_s"] = time.perf_counter() - t0

    per_step = []  # decode rows (tokens past a request's first) per step

    def step(in_window: bool) -> None:
        before = sum(len(r.out) for r in feed.all)
        firsts = sum(1 for r in feed.all if r.out)
        with jax.profiler.TraceAnnotation("bench.step"):
            eng.step()
        if in_window:
            emitted = sum(len(r.out) for r in feed.all) - before
            new_first = sum(1 for r in feed.all if r.out) - firsts
            per_step.append(emitted - new_first)
        feed.refill()

    warm = []  # each warm-up step's seconds: compile or cache load, traffic
    for _ in range(t["warmup_steps"]):
        t0 = time.perf_counter()
        step(False)
        warm.append(time.perf_counter() - t0)
    compiled = eng.compiled_cache_sizes()
    setup["warmup_s"] = sum(warm)
    print("warmup_step_s: " + " ".join(f"{x:.3f}" for x in warm), flush=True)
    before = [(len(r.prompt), len(r.out)) for r in feed.all]

    capture = devtrace.Capture(args.trace, f"{args.work_dir}/trace")
    with gcwatch.Frozen() as gcw:
        capture.start()
        setup_s = time.perf_counter() - args.t_process0
        obs.tracer.clear()
        dropped, w0_us = obs.tracer.dropped, obs.tracer.now_us()
        with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
            t_start = time.perf_counter()
            ends = [t_start]
            while True:
                step(True)
                t_end = time.perf_counter()
                ends.append(t_end)
                if t_end - t_start >= args.seconds:
                    break
        capture.stop()
    w = win.Window(t_start, t_end, len(per_step), t["warmup_steps"])
    if obs.tracer.dropped != dropped:
        raise RuntimeError("the program's tracer dropped events in the window")
    prog_spans = spans.pair(obs.tracer.events(), w0_us, obs.tracer.now_us())
    if eng.compiled_cache_sizes() != compiled:
        raise RuntimeError(f"a step compiled inside the window: {compiled} -> "
                           f"{eng.compiled_cache_sizes()}")
    mem = device.memory_peak_bytes(jax, cell.chips)

    lists = [r.t_tokens for r in feed.all]
    tokens = win.tokens_in(w, lists)
    gaps = win.gaps_in(w, lists)
    e2e = {"setup_s": setup_s, "tokens_per_s": tokens / w.seconds}
    if gaps.size:
        e2e["itl_p95_ms"] = win.percentile(gaps, 95) * 1e3
    prefill_steps = len(spans.durations_ms(prog_spans, "engine.prefill"))
    counts = {
        "tokens": tokens, "itl_gaps": int(gaps.size), "steps": w.steps,
        "prefill_steps": prefill_steps,
        "decode_steps": sum(1 for rows in per_step if rows),
        "prefill_prompt_tokens": sum(
            min(len(r.prompt), t["prefill_len"]) for r in feed.all
            if r.t_tokens and w.inside(r.t_tokens[0])),
        "slots": t["slots"], "prefill_chunk": t["prefill_chunk"],
        "before_window": before,   # (prompt, served) per request at the start
    }
    decode_gemms = [g for rows in per_step if rows
                    for g in work.lm_sparse_step_gemms(cfg, rows)]
    flops = sum(work.lm_token_flops(cfg, min(len(r.prompt), t["prefill_len"])
                                    + j)
                for r in feed.all for j, tt in enumerate(r.t_tokens)
                if j >= 1 and w.inside(tt))
    trace = capture.reduce(cell.chips)
    record = Record(cell=cell, peaks=args.peaks, window=w, counts=counts,
                    work={"decode_gemms": decode_gemms, "window_flops": flops},
                    trace=trace, spans=prog_spans)
    attempted = sum(1 for r in feed.all if any(w.inside(x) for x in r.t_tokens))
    print(f"window: {w.steps} steps from step {w.warmup_steps}, "
          f"{w.seconds:.3f} s, {tokens} tokens, {prefill_steps} prefill steps, "
          f"{counts['decode_steps']} decode steps, {attempted} requests",
          flush=True)
    ms = np.diff(ends) * 1e3
    print("step_ms: min {:.2f} q1 {:.2f} median {:.2f} q3 {:.2f} max {:.2f}"
          .format(*np.percentile(ms, [0, 25, 50, 75, 100])), flush=True)
    print(gcw.summary(), flush=True)

    # --- the check, after the engine's state is freed -----------------
    sample = _sample(feed.all, t["check_requests"], args.seed)
    obs.tracer.clear()
    del eng, feed.eng
    gc.collect()
    t0 = time.perf_counter()
    batch, rows, toks = _check_batch(sample, t["prefill_len"])
    x = ref.hidden(cfg, key, batch)
    mx, at, _ = ref.logit_rows(cfg, key, x, rows, toks)
    compared = {"max_logit_gap": compare.max_logit_gap(mx, at)}
    control = {}
    if args.control:
        xc = ref.hidden(cfg, key, batch, fp8=True)
        _, _, picked = ref.logit_rows(cfg, key, xc, rows, toks, fp8=True)
        _, at_c, _ = ref.logit_rows(cfg, key, x, rows, picked.astype(np.int32))
        control = {"max_logit_gap": compare.max_logit_gap(mx, at_c)}
    print(f"check: {len(sample)} requests, {len(toks)} served tokens against "
          f"the reference in {time.perf_counter() - t0:.1f} s", flush=True)
    return Outcome(end_to_end=e2e, setup=setup, compared=compared,
                   attempted=attempted, failed=0, memory_peak_bytes=mem,
                   record=record, control=control)
