"""Distribution layer: partition rules and divisibility."""
import jax
from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.models.model import build_model
from repro.parallel.sharding import even_spec, param_logical_axes


def test_param_rules_cover_all_archs():
    """Every parameter leaf resolves to a spec of the right rank."""
    for cfg in ARCHS.values():
        m = build_model(cfg)
        tree = jax.eval_shape(lambda m=m: m.init(jax.random.PRNGKey(0)))
        leaves = jax.tree_util.tree_flatten_with_path(tree)[0]
        for path, leaf in leaves:
            keys = tuple(str(getattr(p, "key", p)) for p in path)
            axes = param_logical_axes(keys, len(leaf.shape))
            assert len(axes) == len(leaf.shape), (cfg.name, keys)


class _FakeMesh:
    shape = {"data": 16, "model": 16}


def test_even_spec_drops_nondividing_axes():
    s = even_spec(P("model", "data"), (49155, 1024), _FakeMesh())
    assert s == P(None, "data")
    s = even_spec(P("data", "model"), (1024, 40), _FakeMesh())
    assert s == P("data", None)


def test_even_spec_tuple_axes():
    class M:
        shape = {"pod": 2, "data": 16, "model": 16}
    assert even_spec(P(("pod", "data"), None), (64, 7), M()) == P(("pod", "data"), None)
    assert even_spec(P(("pod", "data"), None), (40, 7), M()) == P(None, None)
