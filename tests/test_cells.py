"""RNN cell dataflow graphs + CSB-weight execution equivalence."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.cells import (
    cell_apply, init_params, init_state, make_cell, rnn_scan,
)
from repro.core import (
    CSBSpec, csb_masks, csb_project, padded_csb_from_dense,
)


@pytest.mark.parametrize("kind", ["lstm", "gru", "lstmp", "ligru", "lnlstmp"])
def test_cell_shapes_finite(kind, rng):
    cell = make_cell(kind, 12, 24, proj_dim=16)
    params = init_params(cell, jax.random.PRNGKey(0))
    xs = jnp.asarray(rng.normal(size=(5, 2, 12)).astype(np.float32))
    ys, st = jax.jit(lambda p, x: rnn_scan(cell, p, x))(params, xs)
    assert np.isfinite(np.asarray(ys)).all()
    out_dim = 16 if kind in ("lstmp", "lnlstmp") else 24
    assert ys.shape == (5, 2, out_dim)


def test_cell_state_dependency(rng):
    """Output at t must depend on input at t-1 (the context link)."""
    cell = make_cell("gru", 8, 16)
    params = init_params(cell, jax.random.PRNGKey(1))
    xs = jnp.asarray(rng.normal(size=(4, 1, 8)).astype(np.float32))
    ys1, _ = rnn_scan(cell, params, xs)
    xs2 = xs.at[0].add(1.0)
    ys2, _ = rnn_scan(cell, params, xs2)
    assert not np.allclose(np.asarray(ys1[-1]), np.asarray(ys2[-1]))


def test_csb_weights_match_masked_dense(rng):
    """cell_apply with PaddedCSB MVM weights == masked dense weights."""
    cell = make_cell("gru", 16, 32)
    params = init_params(cell, jax.random.PRNGKey(2))
    spec = CSBSpec(bm=8, bn=8, prune_rate=0.5)
    dense_params = {}
    csb_params = {}
    for name, w in params.items():
        if w.ndim == 2:
            z = csb_project(w, spec)
            rm, cm = csb_masks(w, spec)
            dense_params[name] = z
            csb_params[name] = padded_csb_from_dense(
                np.asarray(z), 8, 8,
                row_mask=np.asarray(rm), col_mask=np.asarray(cm))
        else:
            dense_params[name] = w
            csb_params[name] = w
    x = jnp.asarray(rng.normal(size=(2, 16)).astype(np.float32))
    st = init_state(cell, (2,))
    y_dense, _ = cell_apply(cell, dense_params, x, st)
    y_csb, _ = cell_apply(cell, csb_params, x, st)
    np.testing.assert_allclose(np.asarray(y_csb), np.asarray(y_dense),
                               rtol=2e-5, atol=2e-5)


def test_param_counts_match_table1():
    """Table 1 weight counts (weights only, bias excluded there)."""
    # MT1 layer1: LSTM 128->256: 4*(128*256 + 256*256 + 256) = 394,240
    cell = make_cell("lstm", 128, 256)
    assert cell.param_count() == 4 * (128 * 256 + 256 * 256 + 256)
    # SR4: GRU 39->256: 3*(39*256 + 256*256 + 256) = 227,328 (~226.6K+0.8K)
    cell = make_cell("gru", 39, 256)
    assert cell.param_count() == 3 * (39 * 256 + 256 * 256 + 256)
