"""Training substrate: optimizer semantics, microbatch equivalence,
gradient compression, loss-goes-down integration."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_reduced
from repro.data.pipeline import DataPipeline, PipelineConfig
from repro.models.transformer import LM
from repro.optim.optimizer import (
    AdamWConfig,
    adamw_init,
    adamw_update,
    cosine_schedule,
    global_norm,
)
from repro.training.train_loop import (
    TrainConfig,
    init_compress_state,
    make_train_step,
)


def test_cosine_schedule_shape():
    cfg = AdamWConfig(lr=1.0, warmup_steps=10, total_steps=110,
                      min_lr_frac=0.1)
    lrs = [float(cosine_schedule(cfg, jnp.int32(s))) for s in
           (0, 5, 10, 60, 110, 200)]
    assert lrs[0] == 0.0
    assert abs(lrs[1] - 0.5) < 1e-6  # mid-warmup
    assert abs(lrs[2] - 1.0) < 1e-6  # peak
    assert lrs[3] < lrs[2]
    assert abs(lrs[4] - 0.1) < 1e-6  # floor
    assert abs(lrs[5] - 0.1) < 1e-6  # stays at floor


def test_adamw_skips_int_leaves():
    params = {"w": jnp.ones((4, 4)), "idx": jnp.zeros((4, 4), jnp.int8)}
    grads = {"w": jnp.ones((4, 4)),
             "idx": np.zeros((4, 4), jax.dtypes.float0)}
    st = adamw_init(params)
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=10,
                      weight_decay=0.0)
    new, st, m = adamw_update(cfg, params, grads, st)
    assert (np.asarray(new["idx"]) == 0).all()
    assert new["idx"].dtype == jnp.int8
    assert not np.allclose(np.asarray(new["w"]), 1.0)  # w moved


def test_adamw_excludes_nmweight_idx_structurally():
    """No AdamW moments/updates may ever be allocated for the idx leaf
    of an NMWeight — excluded by node type, not dtype — while an
    unrelated integer leaf elsewhere keeps its historical pass-through
    behavior (scalar moment placeholder, leaf untouched)."""
    import dataclasses

    from repro.api import NMConfig, sparsify
    from repro.core.nmweight import NMWeight

    w = sparsify(jax.random.normal(jax.random.PRNGKey(0), (8, 4)),
                 NMConfig(2, 4), kernel_policy="off")
    params = {"lin": w, "b": jnp.ones((4,)),
              "counter": jnp.arange(3, dtype=jnp.int32)}  # unrelated int
    st = adamw_init(params)
    # structural exclusion: idx moment is a scalar placeholder, never
    # an idx-shaped buffer
    assert isinstance(st["m"]["lin"], NMWeight)
    assert st["m"]["lin"].idx.shape == ()
    assert st["m"]["lin"].vals.shape == w.vals.shape
    assert st["m"]["counter"].shape == ()  # int leaf: unchanged behavior

    def loss(p):
        x = jnp.ones((2, 8))
        y = x @ p["lin"].to_dense() + p["b"]
        return jnp.sum(y ** 2)

    grads = jax.grad(loss, allow_int=True)(params)
    cfg = AdamWConfig(lr=0.1, warmup_steps=0, total_steps=10,
                      weight_decay=0.0)
    new, st2, _ = adamw_update(cfg, params, grads, st)
    np.testing.assert_array_equal(np.asarray(new["lin"].idx),
                                  np.asarray(w.idx))  # idx bit-identical
    assert new["lin"].idx.dtype == jnp.int8
    assert st2["m"]["lin"].idx.shape == ()  # still no idx-shaped state
    np.testing.assert_array_equal(np.asarray(new["counter"]),
                                  np.asarray(params["counter"]))
    assert not np.allclose(np.asarray(new["lin"].vals),
                           np.asarray(w.vals))  # vals trained
    assert not np.allclose(np.asarray(new["b"]), 1.0)

    # a masked weight's dense w keeps training (recursed, not excluded)
    from repro.core.nmweight import MaskedNMWeight
    mp = {"lin": MaskedNMWeight(w=jnp.ones((8, 4)), nm=NMConfig(2, 4))}
    mst = adamw_init(mp)
    assert mst["m"]["lin"].w.shape == (8, 4)
    mg = {"lin": dataclasses.replace(mp["lin"], w=jnp.ones((8, 4)))}
    mnew, _, _ = adamw_update(cfg, mp, mg, mst)
    assert not np.allclose(np.asarray(mnew["lin"].w), 1.0)


def test_global_norm():
    g = {"a": jnp.ones((3,)) * 2.0, "b": jnp.ones((4,)) * 1.0,
         "i": jnp.zeros((2,), jnp.int8)}
    assert abs(float(global_norm(g)) - 4.0) < 1e-6  # sqrt(12+4)


def test_microbatch_equivalence():
    """grad accumulation over 4 microbatches == single big batch."""
    cfg = get_reduced("codeqwen1.5-7b", sparse=False)
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (8, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    p1, _, m1 = make_train_step(lm, TrainConfig(microbatches=1, remat="none"))(
        params, opt, batch)
    p4, _, m4 = make_train_step(lm, TrainConfig(microbatches=4, remat="none"))(
        params, opt, batch)
    np.testing.assert_allclose(float(m1["loss"]), float(m4["loss"]),
                               rtol=2e-3)
    d = jax.tree.map(
        lambda a, b: float(jnp.max(jnp.abs(a.astype(jnp.float32)
                                           - b.astype(jnp.float32))))
        if jnp.issubdtype(a.dtype, jnp.inexact) else 0.0, p1, p4)
    assert max(jax.tree.leaves(d)) < 5e-2  # bf16 accumulation tolerance


def test_remat_matches_no_remat():
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    _, _, m0 = make_train_step(lm, TrainConfig(remat="none"))(params, opt, batch)
    _, _, m1 = make_train_step(lm, TrainConfig(remat="dots"))(params, opt, batch)
    np.testing.assert_allclose(float(m0["loss"]), float(m1["loss"]), rtol=1e-4)


def test_grad_compression_roundtrip():
    cfg = get_reduced("yi-9b")
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    err = init_compress_state(params)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0,
                                cfg.vocab_size)
    batch = {"tokens": tokens, "labels": tokens}
    step = make_train_step(lm, TrainConfig(grad_compression=True))
    params2, opt2, err2, metrics = step(params, opt, batch, err)
    assert np.isfinite(float(metrics["loss"]))
    # error feedback is non-trivial
    enorm = float(global_norm(err2))
    assert enorm > 0


@pytest.mark.slow
def test_loss_decreases_end_to_end():
    """The (b)-deliverable training driver at micro scale: loss drops."""
    cfg = get_reduced("codeqwen1.5-7b")
    lm = LM(cfg)
    tcfg = TrainConfig(opt=AdamWConfig(lr=3e-3, warmup_steps=5,
                                       total_steps=60),
                       microbatches=1, remat="none")
    step = jax.jit(make_train_step(lm, tcfg))
    params = lm.init(jax.random.PRNGKey(0))
    opt = adamw_init(params)
    pipe = DataPipeline(PipelineConfig(vocab_size=cfg.vocab_size, seq_len=32,
                                       global_batch=8))
    losses = []
    for _ in range(60):
        batch = {k: jnp.asarray(v) for k, v in pipe.next().items()}
        params, opt, metrics = step(params, opt, batch)
        losses.append(float(metrics["loss"]))
    assert np.mean(losses[-10:]) < np.mean(losses[:10]) - 0.2, losses[::10]


def _f32_shapes(jaxpr, out=None):
    """Every f32 value shape in ``jaxpr`` and the jaxprs nested in it."""
    out = set() if out is None else out
    for eqn in jaxpr.eqns:
        for v in eqn.outvars:
            aval = getattr(v, "aval", None)
            if getattr(aval, "dtype", None) == jnp.float32:
                out.add(tuple(aval.shape))
        for p in eqn.params.values():
            for sub in (p if isinstance(p, (list, tuple)) else (p,)):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _f32_shapes(inner, out)
    return out


def test_train_full_width_init_has_no_f32_group_stack():
    """``train --no-reduced`` builds yi-9b's published widths in bf16
    through LM.init, whose dense f32 draws exist one layer at a time."""
    from repro.configs import cut_layers, get_config
    from repro.core.nmweight import NMWeight

    lm = LM(cut_layers(get_config("yi-9b", sparse=True), 2))

    def init():
        return lm.init(jax.random.PRNGKey(0), param_dtype=jnp.bfloat16)

    params = jax.eval_shape(init)
    w_up = params["groups"][0][0]["mlp"]["w_up"]
    assert isinstance(w_up, NMWeight)
    assert w_up.vals.shape == (2, 2048, 11008)
    assert w_up.vals.dtype == jnp.bfloat16
    assert params["embed"]["embedding"].dtype == jnp.bfloat16
    shapes = _f32_shapes(jax.make_jaxpr(init)().jaxpr)
    assert (4096, 11008) in shapes  # one layer's dense draw ...
    assert not any(len(s) == 3 and s[0] == 2 and 11008 in s
                   for s in shapes)  # ... never the group's stack
