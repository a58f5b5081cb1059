"""The engine step traced from inside: the ``engine.step`` span tree, the
prefill row counts and decode context the spans carry, the spans on the
profiler's host line, and the N:M kernels named after their projections.

Everything here runs the reduced yi-9b on the CPU.
"""
import dataclasses
import glob

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.obs as obs
from repro.configs import get_reduced
from repro.models import common
from repro.models.transformer import LM
from repro.serving.engine import Request, ServeEngine, make_serve_steps
from repro.serving.scheduler import Scheduler

PHASES = ("engine.upload", "engine.dispatch", "engine.readback")


@pytest.fixture(autouse=True)
def _isolated_obs():
    obs.reset_for_tests()
    yield
    obs.reset_for_tests()


@pytest.fixture(scope="module")
def yi():
    common.set_compute_dtype(jnp.float32)
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    yield cfg, lm, params
    common.set_compute_dtype(jnp.bfloat16)


def _serve(lm, cfg, params, *, lengths=(8, 3, 12, 5), max_new=3,
           prefill_len=8, **extra):
    eng = ServeEngine(lm, params, slots=2, max_seq=64,
                      prefill_len=prefill_len, **extra)
    rng = np.random.default_rng(5)
    for i, n in enumerate(lengths):
        eng.submit(Request(rid=i, prompt=rng.integers(
            1, cfg.vocab_size, size=n).astype(np.int32), max_new=max_new))
    eng.run()
    return {r.rid: tuple(r.out) for r in eng.finished}, eng


def _tree(events):
    """Completed sync spans as nodes {name, args, t0, t1, kids}, roots
    first, from one thread's B/E events."""
    roots, stack = [], []
    for ev in sorted(events, key=lambda e: e["ts"]):
        if ev["ph"] == "B":
            node = {"name": ev["name"], "args": ev.get("args", {}),
                    "t0": ev["ts"], "kids": []}
            (stack[-1]["kids"] if stack else roots).append(node)
            stack.append(node)
        elif ev["ph"] == "E":
            node = stack.pop()
            assert node["name"] == ev["name"]
            node["t1"] = ev["ts"]
    assert not stack
    return roots


def _expected_real_rows(prompt_len, pos, chunk, prefill_len):
    kept = min(prompt_len, prefill_len)
    real = np.arange(prefill_len) >= prefill_len - kept
    return int(real[pos:pos + chunk].sum())


# ---------------------------------------------------------------------------
# span tree
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("extra", [{}, {"prefill_chunk": 4},
                                   {"paged": True, "prefill_chunk": 4,
                                    "page_size": 4, "pool_pages": 32}],
                         ids=["full", "chunked", "paged"])
def test_step_span_tree(yi, extra):
    """Every engine.step span holds plan and finish as its own children,
    and the device calls hold exactly one upload, dispatch and readback:
    engine.prefill / engine.decode keep their old boundaries."""
    cfg, lm, params = yi
    bundle = obs.enable(obs.Obs.create())
    _, eng = _serve(lm, cfg, params, **extra)
    roots = _tree([e for e in bundle.tracer.events()
                   if e["ph"] in ("B", "E")])
    assert [r["name"] for r in roots] == ["engine.step"] * eng.steps
    calls = 0
    for i, step in enumerate(roots):
        assert step["args"]["step"] == i
        assert {"occupied", "queue"} <= set(step["args"])
        names = [k["name"] for k in step["kids"]]
        assert names[0] == "engine.plan"
        assert set(names) <= {"engine.plan", "engine.upload",
                              "engine.prefill", "engine.decode",
                              "engine.finish"}
        # the paged table snapshot is the only upload outside a call
        assert ("engine.upload" in names) == (
            extra.get("paged", False) and any(
                n in names for n in ("engine.prefill", "engine.decode")))
        for call in step["kids"]:
            if call["name"] not in ("engine.prefill", "engine.decode"):
                assert not call["kids"]
                continue
            calls += 1
            assert [k["name"] for k in call["kids"]] == list(PHASES)
            nxt = names[names.index(call["name"]) + 1]
            assert nxt == "engine.finish"
    assert calls > 0


def test_prefill_and_decode_span_args(yi):
    """engine.prefill carries the rows it computed and the real prompt
    tokens among them; engine.decode the active slots' cache lengths;
    the counter adds up the rows by kind."""
    cfg, lm, params = yi
    bundle = obs.enable(obs.Obs.create())
    lengths = (8, 3, 12, 5)
    _, eng = _serve(lm, cfg, params, lengths=lengths, prefill_chunk=4)
    evs = [e for e in bundle.tracer.events() if e["ph"] == "B"]
    pre = [e["args"] for e in evs if e["name"] == "engine.prefill"]
    dec = [e["args"] for e in evs if e["name"] == "engine.decode"]
    assert all(a["rows"] == 2 * 4 for a in pre)
    # every request's kept prompt is counted once over its chunks
    assert sum(a["real_rows"] for a in pre) == sum(min(n, 8) for n in lengths)
    assert all(0 <= a["real_rows"] <= a["rows"] for a in pre)
    # a decode step's context: each active slot's length, prefill_len
    # after its prefill and one more per token since
    assert all(a["ctx_tokens"] >= 8 * a["active"] for a in dec)
    assert max(a["ctx_tokens"] for a in dec) <= 2 * (8 + 2)
    m = bundle.metrics
    real = m.counter_value("serve_prefill_rows_total", kind="real")
    pad = m.counter_value("serve_prefill_rows_total", kind="pad")
    assert real == sum(a["real_rows"] for a in pre)
    assert real + pad == sum(a["rows"] for a in pre)


@pytest.mark.parametrize("prefill_len,chunk", [(8, 8), (8, 4), (8, 2)],
                         ids=["unchunked", "chunk4", "chunk2"])
def test_real_rows_count_prompt_tokens(prefill_len, chunk):
    """real_rows counts the kept prompt tokens in each chunk, without the
    left padding of short prompts, the cut head of long ones, or empty
    slots."""
    lengths = [1, 3, 8, 13]       # short, short, exact, truncated
    sched = Scheduler(slots=3, max_seq=32, prefill_len=prefill_len,
                      prefill_chunk=chunk)
    for i, n in enumerate(lengths):
        sched.submit(Request(rid=i, prompt=np.arange(1, n + 1,
                                                     dtype=np.int32),
                             max_new=1))
    seen = {}
    while sched.has_work:
        pf = sched.plan_prefill()
        if pf is None:
            dc = sched.plan_decode()
            sched.finish_decode(dc, np.zeros(3, np.int32))
            continue
        want = 0
        for i in pf.active:
            slot = sched.slots[i]
            n = len(slot.req.prompt)
            want += _expected_real_rows(n, slot.pos, chunk, prefill_len)
            seen[slot.req.rid] = seen.get(slot.req.rid, 0) + \
                _expected_real_rows(n, slot.pos, chunk, prefill_len)
        assert pf.real_rows == want
        assert pf.tokens.size == 3 * chunk
        sched.finish_prefill(pf, np.zeros(3, np.int32))
    assert seen == {i: min(n, prefill_len) for i, n in enumerate(lengths)}


# ---------------------------------------------------------------------------
# obs off / on
# ---------------------------------------------------------------------------


@pytest.mark.parametrize("extra", [{}, {"prefill_chunk": 4}],
                         ids=["full", "chunked"])
def test_token_streams_identical_with_obs_off_and_on(yi, extra):
    cfg, lm, params = yi
    off, eng_off = _serve(lm, cfg, params, **extra)
    assert eng_off.obs is None
    obs.enable(obs.Obs.create())
    on, eng_on = _serve(lm, cfg, params, **extra)
    assert on == off
    assert eng_on.compiled_cache_sizes() == {"prefill": 1, "decode": 1}


def test_obs_off_enters_no_annotation_and_records_nothing(yi, monkeypatch):
    """Off, a step neither builds a tracer nor enters a profiler
    annotation; on, every span enters one."""
    entered = []

    class Counting:
        def __init__(self, name, **kw):
            entered.append(name)

        def __enter__(self):
            return self

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", Counting)
    cfg, lm, params = yi
    _, eng = _serve(lm, cfg, params)
    assert eng.obs is None and eng.scheduler.obs is None
    assert entered == []
    bundle = obs.enable(obs.Obs.create())
    _serve(lm, cfg, params)
    spans = [e["name"] for e in bundle.tracer.events() if e["ph"] == "B"]
    assert sorted(entered) == sorted(spans)


def test_spans_land_on_the_profilers_host_line(yi, tmp_path):
    """A jax.profiler trace taken while the engine runs holds the
    program's spans on the host plane, beside JAX's own."""
    from jax.profiler import ProfileData

    cfg, lm, params = yi
    obs.enable(obs.Obs.create())
    _serve(lm, cfg, params, max_new=2)          # compile outside the trace
    jax.profiler.start_trace(str(tmp_path))
    try:
        _serve(lm, cfg, params, max_new=2)
    finally:
        jax.profiler.stop_trace()
    files = glob.glob(str(tmp_path / "**" / "*.xplane.pb"), recursive=True)
    assert len(files) == 1
    names = set()
    for plane in ProfileData.from_file(files[0]).planes:
        if plane.name == "/host:CPU":
            for line in plane.lines:
                names.update(e.name for e in line.events)
    assert {"engine.step", "engine.plan", "engine.upload", "engine.dispatch",
            "engine.readback", "engine.finish", "engine.prefill",
            "engine.decode"} <= names


# ---------------------------------------------------------------------------
# kernel names
# ---------------------------------------------------------------------------


def test_decode_step_names_each_kernel_after_its_projection(yi):
    """The lowered decode step holds one jitted kernel per projection,
    ``nm_spmm_pallas_decode_<target>_<proj>``."""
    cfg, _, _ = yi
    cfg = dataclasses.replace(cfg, sparsity=dataclasses.replace(
        cfg.sparsity, use_kernel=True))
    lm = LM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    caches = jax.eval_shape(lambda: lm.init_cache(2, 32))
    _, decode_step = make_serve_steps(lm)
    text = decode_step.lower(
        params, jax.ShapeDtypeStruct((2, 1), jnp.int32), caches,
        jax.ShapeDtypeStruct((2,), jnp.int32)).as_text()
    for proj in ("attn_proj_wq", "attn_proj_wk", "attn_proj_wv",
                 "attn_proj_wo", "ffn_w_up", "ffn_w_gate", "ffn_w_down"):
        assert f"nm_spmm_pallas_decode_{proj}" in text, proj
