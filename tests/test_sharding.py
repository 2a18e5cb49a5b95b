"""Distribution layer: partition rules, divisibility, and an 8-device
subprocess check that a sharded train step compiles AND matches the
single-device result numerically (DP/TP equivalence)."""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from jax.sharding import PartitionSpec as P

from repro.configs import ARCHS
from repro.models.model import build_model
from repro.parallel.sharding import (RULES_MULTI_POD, RULES_SINGLE_POD,
                                     even_spec, param_logical_axes)


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


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax, jax.numpy as jnp
    import numpy as np
    from repro.configs import ARCHS, smoke_config
    from repro.models.model import build_model
    from repro.train.optimizer import OptimizerConfig
    from repro.train.train_step import init_train_state, make_train_step
    from repro.parallel.sharding import use_mesh, param_pspec_tree
    from jax.sharding import NamedSharding, PartitionSpec as P

    sc = smoke_config(ARCHS["qwen2.5-3b"]).with_(dtype="float32",
                                                 param_dtype="float32")
    m = build_model(sc)
    opt = OptimizerConfig(warmup_steps=1, decay_steps=10)
    key = jax.random.PRNGKey(0)
    tokens = jax.random.randint(key, (8, 32), 0, sc.vocab_size)
    batch = {"tokens": tokens, "targets": tokens}

    # single-device reference
    state0 = init_train_state(m, key, opt)
    step = make_train_step(m, opt, microbatches=1)
    s_ref, met_ref = jax.jit(step)(state0, batch)

    # 2x4 mesh (data=2, model=4)
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((2, 4), ("data", "model"))
    with use_mesh(mesh):
        state1 = init_train_state(m, key, opt)
        pspecs = param_pspec_tree(
            jax.tree.map(lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype),
                         state1.params), mesh)
        shard = lambda t, s: jax.device_put(t, NamedSharding(mesh, s))
        params = jax.tree.map(shard, state1.params, pspecs)
        opt_state = {"m": jax.tree.map(shard, state1.opt["m"], pspecs),
                     "v": jax.tree.map(shard, state1.opt["v"], pspecs),
                     "step": state1.opt["step"]}
        from repro.train.train_step import TrainState
        state1 = TrainState(params=params, opt=opt_state, step=state1.step)
        sharded_batch = jax.tree.map(
            lambda x: jax.device_put(x, NamedSharding(mesh, P("data", None))),
            batch)
        s_mesh, met_mesh = jax.jit(step)(state1, sharded_batch)

    l0 = float(met_ref["loss"]); l1 = float(met_mesh["loss"])
    diffs = [float(jnp.max(jnp.abs(a - b)))
             for a, b in zip(jax.tree.leaves(s_ref.params),
                             jax.tree.leaves(s_mesh.params))]
    print(json.dumps({"loss_ref": l0, "loss_mesh": l1,
                      "max_param_diff": max(diffs)}))
""")


def test_sharded_train_step_matches_single_device():
    out = subprocess.run(
        [sys.executable, "-c", _SUBPROC], capture_output=True, text=True,
        env={"PYTHONPATH": "src", "PATH": "/usr/bin:/bin",
             "HOME": os.environ.get("HOME", "/"), "JAX_PLATFORMS": "cpu"},
        timeout=600, cwd=os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))))
    assert out.returncode == 0, out.stderr[-3000:]
    res = json.loads(out.stdout.strip().splitlines()[-1])
    assert res["loss_ref"] == pytest.approx(res["loss_mesh"], rel=1e-4)
    assert res["max_param_diff"] < 5e-4
