"""The plain float32 references against the program's models, at the
smoke configurations on the CPU, from the same weights and rows."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, reference
from chipbench.reference.common import key_from_seed, make_weights
from conftest import TINY


def _program(family):
    from repro.configs import get_config
    return get_config({"dense": "olmo-1b", "rwkv6": "rwkv6-1.6b"}[family],
                      smoke=True)


@pytest.mark.parametrize("family", ["dense", "rwkv6"])
def test_reference_matches_program(family):
    from repro.models import zoo
    c = TINY[family]
    cfg = _program(family)
    mod = reference.family(family)
    params = make_weights(mod.layout(c), key_from_seed(2**31 + 3))
    rows = data.synthetic_lm(11, 0, c["vocab_size"], 64, 2)
    batch = {k: jnp.asarray(v) for k, v in rows.items()}

    def program_loss(p):
        return zoo.loss_fn(cfg, p, batch)[0]

    def reference_loss(p):
        return mod.loss(c, p, batch["tokens"], batch["targets"])

    lp, gp = jax.value_and_grad(program_loss)(params)
    lr, gr = jax.value_and_grad(reference_loss)(params)
    np.testing.assert_allclose(float(lp), float(lr), rtol=1e-5)
    for a, b in zip(jax.tree.leaves(gp), jax.tree.leaves(gr)):
        a, b = np.asarray(a), np.asarray(b)
        assert np.linalg.norm(a - b) <= 1e-4 * np.linalg.norm(b) + 1e-9


@pytest.mark.parametrize("family", ["dense", "rwkv6"])
def test_layout_is_the_programs_tree(family):
    from repro.models import zoo
    layout = reference.family(family).layout(TINY[family])
    shapes = {"/".join(str(getattr(p, "key", p)) for p in path): x.shape
              for path, x in jax.tree_util.tree_flatten_with_path(
                  zoo.abstract(_program(family)))[0]}
    assert shapes == {p: s for p, (s, _) in layout.items()}


def test_traffic_rows_are_the_programs():
    from repro.data import DataConfig, SyntheticLM
    src = SyntheticLM(DataConfig(seq_len=32, global_batch=3, vocab=512,
                                 seed=2**31 + 9))
    for step in (0, 5):
        got = src.batch_at(step)
        want = data.synthetic_lm(2**31 + 9, step, 512, 32, 3)
        for k in ("tokens", "targets"):
            np.testing.assert_array_equal(got[k], want[k])


def test_seeds_past_32_bits_give_other_weights():
    a, b = (jax.random.key_data(key_from_seed(s)) for s in (7, 7 + 2**32))
    assert not np.array_equal(np.asarray(a), np.asarray(b))
