"""Sharded serving: token parity with the single-device engine on a
forced 8-device host mesh, plus unit tests for the head-aware TP spec
rules.

The parity matrix (float 2:4, int8 2:4, mixed 2:4/1:4, kv-head-sharded,
paged-KV) runs real multi-device CPU execution in a subprocess (device count must
be set before jax initializes — same pattern as test_sharding /
test_moe_distributed); each variant asserts identical token ids AND that
the compiled-step caches hold exactly one entry after serving (zero
recompiles after warmup)."""
import dataclasses
import os
import subprocess
import sys

import jax
import pytest

from repro.configs import get_reduced
from repro.models.transformer import LM
from repro.parallel.sharding import serve_param_pspecs, serve_tp_plan

_SUBPROC = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro.models import common
common.set_compute_dtype(jnp.float32)  # exactness for parity
from repro import compat
from repro.configs import get_reduced
from repro.configs.base import SparsityConfig
from repro.core.sparsity import NMConfig
from repro.models.transformer import LM
from repro.serving.engine import Request, ServeEngine, ShardedServeEngine

rng = np.random.default_rng(0)
mesh = compat.make_mesh((2, 4), ("data", "model"))

def check(cfg, quantize=None, tag="", chunk=None):
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    prompts = [rng.integers(0, cfg.vocab_size, size=8).astype(np.int32)
               for _ in range(5)]
    kw = dict(slots=2, max_seq=64, prefill_len=8, quantize=quantize,
              prefill_chunk=chunk)
    def serve(make):
        eng = make()
        for i, p in enumerate(prompts):
            eng.submit(Request(rid=i, prompt=p, max_new=4 + i))
        return {r.rid: tuple(r.out) for r in eng.run()}, eng
    single, _ = serve(lambda: ServeEngine(lm, params, **kw))
    shard, es = serve(
        lambda: ShardedServeEngine(lm, params, mesh=mesh, **kw))
    assert shard == single, (tag, single, shard)
    cs = es.compiled_cache_sizes()
    assert cs in ({"prefill": 1, "decode": 1},
                  {"prefill": -1, "decode": -1}), (tag, cs)
    print(f"OKVARIANT {tag} {es.tp_plan.shard_attn:d}"
          f"{es.tp_plan.shard_kv:d}{es.tp_plan.shard_ffn:d}")

cfg = get_reduced("yi-9b")  # 2:4 compressed by default
check(cfg, tag="float24")
check(cfg, tag="float24-chunked", chunk=4)
check(cfg, quantize="int8", tag="int8")
mixed = dataclasses.replace(cfg, sparsity=SparsityConfig(
    nm=NMConfig(2, 4), mode="compressed",
    targets=("ffn", "attn_proj"),
    nm_overrides=(("attn_proj", NMConfig(1, 4)),)))
check(mixed, tag="mixednm")
# kv_heads divisible by tp: the KV cache actually shards on its head axis
kvblk, rep = cfg.plan[0]
kvcfg = dataclasses.replace(cfg, plan=((dataclasses.replace(
    kvblk, mixer=dataclasses.replace(kvblk.mixer, kv_heads=4)), rep),))
check(kvcfg, tag="kvsharded")
# kernel-on: token parity with the Pallas decode family engaged, and the
# decode compiles (single AND sharded) routed every GEMM to Pallas —
# asserted through the per-family dispatch counters, not the (bounded,
# evictable) record history
from repro.kernels import registry
kcfg = dataclasses.replace(cfg, sparsity=SparsityConfig(
    nm=NMConfig(2, 4), mode="compressed", use_kernel=True))
registry.clear_history()
check(kcfg, tag="kernel24")
counts = registry.dispatch_counts("nm_matmul_decode")
assert counts and sum(counts.values()) > 0, counts
bad = {k: v for k, v in counts.items() if not k[1].startswith("pallas")}
assert not bad, bad
print(f"KERNELDECODE ok {sum(int(v) for v in counts.values())}")
# block-sparse masked model: the sharded engine must serve a
# mask-bearing config token-identically to the single-device engine —
# the mask-aware decode family runs inside the shard_map'd steps on
# every shard (chunked prefill included) — with zero recompiles
from repro.configs.base import AttnConfig
from repro.kernels.blocksparse_attn.mask import MaskSpec
def _mask_blk(b):
    if not isinstance(b.mixer, AttnConfig):
        return b
    return dataclasses.replace(b, mixer=dataclasses.replace(
        b.mixer, mask=MaskSpec("local", block=8, window=12), window=None))
mcfg = dataclasses.replace(cfg, plan=tuple(
    ((tuple(_mask_blk(x) for x in e) if isinstance(e, tuple)
      else _mask_blk(e)), r) for e, r in cfg.plan))
registry.clear_history()
check(mcfg, tag="blocksparse", chunk=4)
bs = registry.dispatch_counts("bs_attention_decode")
assert bs and sum(bs.values()) > 0, bs
print(f"BSDECODE ok {sum(int(v) for v in bs.values())}")
# paged: the sharded PAGED engine (block-table gather, one page sub-pool
# per data shard, head-sharded pool pages via the unchanged cache specs)
# against the single-device SLOT engine — cross-engine AND cross-layout
# token parity in one shot. Shared prompt prefixes must actually hit the
# per-shard prefix caches. kvcfg so the pool's head axis really shards.
lm = LM(kvcfg)
params = lm.init(jax.random.PRNGKey(0))
pp = [rng.integers(0, kvcfg.vocab_size, size=8).astype(np.int32)
      for _ in range(5)]
for p in pp[1:]:
    p[:4] = pp[0][:4]  # every request shares the first page
kw = dict(slots=2, max_seq=64, prefill_len=8, prefill_chunk=4)
def serve_paged(make):
    eng = make()
    for i, p in enumerate(pp):
        eng.submit(Request(rid=i, prompt=p, max_new=4 + i))
    return {r.rid: tuple(r.out) for r in eng.run()}, eng
single, _ = serve_paged(lambda: ServeEngine(lm, params, **kw))
paged, ep = serve_paged(
    lambda: ShardedServeEngine(lm, params, mesh=mesh, paged=True, **kw))
assert paged == single, (single, paged)
cs = ep.compiled_cache_sizes()
assert cs in ({"prefill": 1, "decode": 1},
              {"prefill": -1, "decode": -1}), cs
assert ep.page_manager.groups == 2  # one sub-pool per data shard
st = ep.throughput_stats()
assert st["prefix_hit_pages"] >= 1, st  # shared page reused on-shard
print(f"OKVARIANT paged {ep.tp_plan.shard_attn:d}"
      f"{ep.tp_plan.shard_kv:d}{ep.tp_plan.shard_ffn:d}")
# observability on: the same paged serve with the tracer + metrics
# attached must produce byte-identical tokens and still zero recompiles
# (obs is host-side only; device work is untouched)
import repro.obs as obs_mod
bundle = obs_mod.enable(obs_mod.Obs.create())
single_o, _ = serve_paged(lambda: ServeEngine(lm, params, **kw))
paged_o, eo = serve_paged(
    lambda: ShardedServeEngine(lm, params, mesh=mesh, paged=True, **kw))
obs_mod.disable()
assert single_o == single and paged_o == single, (single, paged_o)
cs = eo.compiled_cache_sizes()
assert cs in ({"prefill": 1, "decode": 1},
              {"prefill": -1, "decode": -1}), cs
snap = bundle.metrics.snapshot()
assert snap["counters"].get("sched_admissions_total", 0) >= 10, snap
assert any(k.startswith("page_allocs_total")
           for k in snap["counters"]), snap
evs = bundle.tracer.events()
assert any(e["ph"] == "b" for e in evs), "no request spans traced"
assert any(e["name"] == "engine.decode" for e in evs), "no decode spans"
print("OBSVARIANT ok")
# params created already sharded (build_lm(mesh=...), as `serve --mesh`
# builds them): the single-device init's values, in the shardings the
# sharded engine keeps, and the same tokens
from repro.launch.serve import build_lm
from repro.parallel.sharding import serve_param_shardings, serve_tp_plan
lm_s, p_sh = build_lm("yi-9b", mesh=mesh)
_, p_one = build_lm("yi-9b")
want = serve_param_shardings(p_one, mesh, serve_tp_plan(lm_s.cfg, 4))
for a, b, sh in zip(jax.tree.leaves(p_sh), jax.tree.leaves(p_one),
                    jax.tree.leaves(want)):
    assert a.sharding.is_equivalent_to(sh, a.ndim), (a.sharding, sh)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
kw = dict(slots=2, max_seq=64, prefill_len=8)
def serve_plain(eng):
    for i, p in enumerate(pp):
        eng.submit(Request(rid=i, prompt=p, max_new=4))
    return {r.rid: tuple(r.out) for r in eng.run()}
assert serve_plain(ShardedServeEngine(lm_s, p_sh, mesh=mesh, **kw)) == \
    serve_plain(ServeEngine(lm_s, p_one, **kw))
print("SHARDEDINIT ok")
print("RESULT ok")
"""


@pytest.fixture(scope="module")
def subproc():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    proc = subprocess.run([sys.executable, "-c", _SUBPROC], env=env,
                          capture_output=True, text=True, timeout=1800)
    assert proc.returncode == 0, proc.stderr[-4000:]
    return proc.stdout


def test_sharded_engine_token_parity(subproc):
    variants = [l.split()[1] for l in subproc.splitlines()
                if l.startswith("OKVARIANT")]
    assert variants == ["float24", "float24-chunked", "int8", "mixednm",
                        "kvsharded", "kernel24", "blocksparse", "paged"]
    assert "RESULT ok" in subproc


def test_kernel_variant_decodes_on_pallas(subproc):
    """The use_kernel=True variant must have routed its decode-family
    GEMMs to the Pallas impls in both engines (asserted in-subprocess
    through the per-family dispatch counters; the marker line carries
    the dispatch count)."""
    assert "KERNELDECODE ok" in subproc


def test_blocksparse_variant_routes_decode_family(subproc):
    """The mask-bearing variant's sharded serve must have routed its
    attention through the bs_attention_decode family (mask-aware decode
    path) — asserted in-subprocess via the dispatch counters."""
    assert "BSDECODE ok" in subproc


def test_obs_on_sharded_parity_and_zero_recompiles(subproc):
    """With observability enabled, the sharded paged serve must emit the
    same token streams as obs-off, keep the compiled caches at one entry
    each, and actually record request spans + metrics (asserted
    in-subprocess)."""
    assert "OBSVARIANT ok" in subproc


def test_sharded_init_matches_single_device(subproc):
    """``build_lm(mesh=...)`` creates every param in the shard the
    sharded engine keeps it in, with the single-device init's values
    and the same served tokens (asserted in-subprocess)."""
    assert "SHARDEDINIT ok" in subproc


def test_kv_sharded_variant_actually_sharded_kv(subproc):
    """The kvsharded variant must have sharded attention AND kv heads;
    the stock reduced config (kv_heads=1) must keep KV replicated."""
    flags = {l.split()[1]: l.split()[2] for l in subproc.splitlines()
             if l.startswith("OKVARIANT")}
    assert flags["float24"] == "101"   # attn + ffn sharded, kv replicated
    assert flags["kvsharded"] == "111"  # kv cache sharded on heads too


# ---------------------------------------------------------------------------
# spec-rule unit tests (single device, no lowering)
# ---------------------------------------------------------------------------


class _FakeMesh:
    axis_names = ("data", "model")

    class devices:  # noqa: D106
        shape = (2, 4)
        size = 8


def test_serve_tp_plan_rejects_moe_and_state_mixers():
    with pytest.raises(NotImplementedError, match="MoE"):
        serve_tp_plan(get_reduced("deepseek-v2-lite-16b"), 4)
    with pytest.raises(NotImplementedError, match="attention"):
        serve_tp_plan(get_reduced("rwkv6-3b"), 4)


def test_serve_tp_plan_head_aware_fallbacks():
    cfg = get_reduced("yi-9b")  # q=8, kv=1, d_ff=256
    plan = serve_tp_plan(cfg, 4)
    assert plan.shard_attn and plan.shard_ffn and not plan.shard_kv
    assert plan.reduce_tags == frozenset({"attn_out", "ffn_down"})
    # tp that does not divide q_heads: attention stays replicated (no
    # psum tag), ffn still shards
    plan3 = serve_tp_plan(cfg, 3)
    assert not plan3.shard_attn and "attn_out" not in plan3.reduce_tags
    # tp=1 never shards
    p1 = serve_tp_plan(cfg, 1)
    assert not (p1.shard_attn or p1.shard_kv or p1.shard_ffn)


def test_serve_tp_plan_gqa_replicated_kv_needs_mqa():
    """q-sharding over replicated KV is only sound for kv_heads == 1: a
    shard's contiguous q-head slice lies in one *global* KV group, but
    the local (hkv, g) reshape would pair it round-robin across all KV
    heads. kv_heads=2 at tp=4 must therefore fall back to replicated
    attention, not serve wrong tokens."""
    cfg = get_reduced("yi-9b")
    blk, rep = cfg.plan[0]
    cfg2 = dataclasses.replace(cfg, plan=((dataclasses.replace(
        blk, mixer=dataclasses.replace(blk.mixer, kv_heads=2)), rep),))
    plan = serve_tp_plan(cfg2, 4)
    assert not plan.shard_attn and not plan.shard_kv
    assert "attn_out" not in plan.reduce_tags
    # ...while kv_heads divisible by tp shards both, grouped locally
    cfg4 = dataclasses.replace(cfg, plan=((dataclasses.replace(
        blk, mixer=dataclasses.replace(blk.mixer, kv_heads=4)), rep),))
    plan4 = serve_tp_plan(cfg4, 4)
    assert plan4.shard_attn and plan4.shard_kv


def test_serve_param_pspecs_co_shard_compressed_pair():
    """vals and idx of every TP-sharded NMWeight carry the same spec
    (the compressed pair moves together), and row-parallel splits land
    on N:M group boundaries."""
    from repro.core.nmweight import NMWeight

    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    plan = serve_tp_plan(cfg, 4)
    specs = serve_param_pspecs(params, _FakeMesh, plan)
    seen_col = seen_row = 0
    flat = jax.tree_util.tree_flatten_with_path(
        specs, is_leaf=lambda x: isinstance(x, NMWeight))[0]
    for path, leaf in flat:
        if not isinstance(leaf, NMWeight):
            continue
        assert tuple(leaf.vals) == tuple(leaf.idx), path  # co-sharded
        # scan-stacked weights carry a leading None axis — compare the
        # logical (in, out) tail
        tail = tuple(leaf.vals)[-2:]
        if tail == (None, "model"):
            seen_col += 1
        if tail == ("model", None):
            seen_row += 1
    assert seen_col and seen_row  # both parallelism flavours present


def test_serve_param_pspecs_rejects_misaligned_row_split():
    """A row-parallel compressed weight whose per-shard slice would cut
    an N:M group in half must be refused loudly."""
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = jax.eval_shape(lambda: lm.init(jax.random.PRNGKey(0)))
    plan = dataclasses.replace(serve_tp_plan(cfg, 4), tp=64)
    with pytest.raises(ValueError, match="group boundaries"):
        serve_param_pspecs(params, _FakeMesh, plan)