"""Weights from the seed: the program's tree, drawn by shape, holds each
role's own tensor, the one the reference draws for that role alone."""
import jax
import jax.numpy as jnp
import numpy as np

from harness import weights as wg
from runs import tiny_cell


def test_uniform_ints_cover_their_range():
    q = np.asarray(wg.uniform_ints(jax.random.PRNGKey(5), (4000,), -3, 4))
    assert q.dtype == np.int32 and set(q.tolist()) == set(range(-3, 4))


def test_nm_weight_keeps_n_ascending_positions_per_block():
    vals, idx = wg.nm_weight(jax.random.PRNGKey(1), 64, 24, 2, 4, 0.1,
                             jnp.bfloat16)
    i = np.asarray(idx).reshape(16, 2, 24)
    assert vals.shape == idx.shape == (32, 24)
    assert ((i[:, 0] < i[:, 1]) & (i[:, 0] >= 0) & (i[:, 1] < 4)).all()


def test_cnn_weights_drawn_by_shape_are_each_roles_own():
    from systems import cnn_offline

    cell = tiny_cell("cnn")
    cfg, ref = cell.config, cell.reference()
    model = cnn_offline.program_model(cfg)
    key = wg.seed_key(2 ** 35 + 9)
    params = jax.jit(cnn_offline.param_builder(model, cfg, ref))(key)
    convs = params["convs"]
    for role in ("conv1", "s2b1_3x3", "s2b2_3x3"):
        want = ref.make(cfg, key, role, jnp.bfloat16)
        got = convs[role]
        got = (got.vals, got.idx) if isinstance(want, tuple) else got
        for a, b in zip(jax.tree_util.tree_leaves(got),
                        jax.tree_util.tree_leaves(want)):
            assert (np.asarray(a) == np.asarray(b)).all(), role
