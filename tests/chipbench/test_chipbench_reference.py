"""The plain float32 references against the program's models, at the
smoke configurations on the CPU, from the same weights and rows; every
family of ``chipbench/reference/`` is a case, at its module's ``TINY``,
and only its own module names it."""

import re
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import data, reference
from chipbench.reference.common import key_from_seed, make_weights

ROOT = Path(__file__).resolve().parents[2]
FAMILIES = reference.families()


def _program(family):
    from repro.configs import get_config
    tiny = reference.family(family).TINY
    return get_config(tiny["arch"], smoke=tiny["smoke"])


@pytest.mark.parametrize("family", FAMILIES)
def test_reference_matches_program(family):
    from repro.models import zoo
    c = reference.family(family).TINY
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


@pytest.mark.parametrize("family", FAMILIES)
def test_layout_is_the_programs_tree(family):
    from repro.models import zoo
    mod = reference.family(family)
    layout = mod.layout(mod.TINY)
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


@pytest.mark.parametrize("family", FAMILIES)
def test_only_the_family_module_names_its_family(family):
    """The harness asks a family's module for all it knows of it, so
    adding a family is adding its module: no other code under
    ``chipbench/`` names it (the configuration files do)."""
    word = re.compile(rf"(?<![A-Za-z0-9]){re.escape(family)}(?![A-Za-z0-9])",
                      re.IGNORECASE)
    own = ROOT / "chipbench" / "reference" / f"{family}.py"
    named = [str(p.relative_to(ROOT))
             for p in (ROOT / "chipbench").rglob("*.py")
             if p != own and word.search(p.read_text())]
    assert named == []


@pytest.mark.parametrize("family", FAMILIES)
def test_family_module_has_what_the_harness_asks(family):
    mod = reference.family(family)
    c = mod.TINY
    assert c["family"] == family
    assert mod.forward_macs(c, 64) > 0
    cfg = _program(family)
    for attr, value in mod.program_settings(c).items():
        assert getattr(cfg, attr) == value, attr
