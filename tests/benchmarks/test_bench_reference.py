"""The plain references against the program's own training models at tiny
sizes: same weights, same tokens, float32 on the CPU."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from benchmarks import spec


@pytest.mark.parametrize("family,preset_cfg", [
    ("mixtral", "mixtral-8x7b-1chip"),
    ("gpt_neox", "pythia-6.9b-4chip"),
])
def test_reference_matches_the_programs_model(family, preset_cfg):
    import json
    import os

    fam = spec.load_family(family)
    with open(os.path.join(spec.HERE, "configs", preset_cfg + ".json")) as f:
        cfg = json.load(f)
    model_cfg = fam.model_config(cfg, rehearsal=True)
    model = fam.train_model(model_cfg)
    params = jax.jit(model.init)(jax.random.key(3))
    # biases and norm offsets start at zero: perturb every leaf so that a
    # reference that dropped one would show
    leaves, tree = jax.tree.flatten(params)
    keys = jax.random.split(jax.random.key(4), len(leaves))
    params = jax.tree.unflatten(tree, [
        p + 0.05 * jax.random.normal(k, p.shape, p.dtype)
        for p, k in zip(leaves, keys)
    ])
    ids = jnp.asarray(
        np.random.default_rng(0).integers(0, model_cfg.vocab_size, (2, 48)), jnp.int32
    )
    with jax.default_matmul_precision("highest"):
        want = jax.jit(model.__call__)(params, ids)
        ref_cfg = fam.reference_config(model_cfg)
        got = jax.jit(lambda p, i: fam.reference.forward_logits(p, ref_cfg, i))(params, ids)
        want_loss = jax.jit(model.loss)(params, ids, ids)
        got_loss = jax.jit(lambda p, i: fam.reference.loss(p, ref_cfg, i))(params, ids)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=2e-4, atol=2e-4)
    if family == "mixtral":
        # the program's training loss adds the router's load-balancing term;
        # the reference is the language-model loss alone
        assert float(got_loss) <= float(want_loss) + 1e-4
        assert abs(float(got_loss) - float(want_loss)) < 0.05 * abs(float(want_loss))
    else:
        assert abs(float(got_loss) - float(want_loss)) < 1e-4 * abs(float(want_loss))
