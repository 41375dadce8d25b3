"""The premise of ``tests/_programs.py``: the model built and scored as one
jitted program agrees with the op-by-op path to within float32 rounding, and
no closer. A leaf moves by a few 1e-9 and the loss by an ulp or two, so a
comparison builds both of its sides the same way."""

import jax
import numpy as np
import pytest

from __graft_entry__ import _make_model_and_batch

from .. import _programs as programs


@pytest.mark.parametrize("na", [False, True], ids=["ci", "na"])
def test_one_program_agrees_with_the_op_by_op_path(na):
    model, batch = _make_model_and_batch(na=na)
    key = jax.random.PRNGKey(0)
    eager = model.init(key, batch)
    jitted = programs.init(model, key, batch)
    assert jax.tree_util.tree_structure(jitted) == jax.tree_util.tree_structure(eager)
    for (path, a), b in zip(jax.tree_util.tree_flatten_with_path(jitted)[0], jax.tree_util.tree_leaves(eager)):
        assert a.shape == b.shape and a.dtype == b.dtype, path
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), rtol=0, atol=1e-7, err_msg=str(path))
    want = np.float32(model.apply(eager, batch).loss)
    got = np.float32(programs.loss(model, jitted, batch))
    assert np.isfinite(want) and abs(got - want) <= 2 * np.spacing(abs(want))
