"""Elastic scaling: checkpoints are logical arrays — a snapshot taken under
one device layout restores under another (the re-shard happens at
device_put against the new mesh's NamedShardings)."""

import json
import os
import subprocess
import sys
import textwrap

import hypothesis
import hypothesis.strategies as st
import jax.numpy as jnp
import numpy as np
import pytest

from repro.train import checkpoint as ckpt
from repro.utils.tree import tree_allclose


@hypothesis.given(
    shapes=st.lists(
        st.tuples(st.integers(1, 8), st.integers(1, 8)), min_size=1, max_size=5),
    dtype=st.sampled_from(["float32", "int32", "bfloat16"]),
    step=st.integers(0, 10**9),
)
@hypothesis.settings(max_examples=20, deadline=None)
def test_checkpoint_roundtrip_property(tmp_path_factory, shapes, dtype, step):
    tmp = tmp_path_factory.mktemp("ck")
    rng = np.random.default_rng(0)
    tree = {f"leaf{i}": jnp.asarray(rng.normal(size=s).astype("float32")).astype(dtype)
            for i, s in enumerate(shapes)}
    path = str(tmp / "c.msgpack")
    ckpt.save(path, tree, step=step)
    loaded, got_step = ckpt.load(path, template=tree)
    assert got_step == step
    assert tree_allclose(tree, loaded, rtol=0, atol=0)


_SUBPROC = textwrap.dedent("""
    import os, sys, json
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%d"
    import jax, jax.numpy as jnp
    from repro import configs
    from repro.dist import sharding
    from repro.train import checkpoint as ckpt

    path, mode = sys.argv[1], sys.argv[2]
    model = configs.get("qwen3-1.7b").make_smoke()
    params = model.init(jax.random.PRNGKey(0))
    mesh = jax.make_mesh((%d, %d), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
    sh = sharding.make_param_shardings(mesh, params)
    if mode == "save":
        placed = jax.tree_util.tree_map(jax.device_put, params, sh)
        ckpt.save(path, placed, step=7)
        print(json.dumps({"ok": True}))
    else:  # restore under THIS (different) mesh
        restored, step = ckpt.load(path, template=params, shardings=sh)
        loss, _ = model.loss(restored, {
            "tokens": jnp.zeros((4, 16), jnp.int32),
            "labels": jnp.ones((4, 16), jnp.int32)})
        print(json.dumps({"ok": True, "step": step, "loss": float(loss)}))
""")


@pytest.mark.slow
def test_elastic_restore_across_device_counts(tmp_path):
    """Save sharded on a (2,4)/8-device mesh; restore + run on (2,2)/4."""
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    path = str(tmp_path / "elastic.msgpack")

    save_src = _SUBPROC % (8, 2, 4)
    p1 = subprocess.run([sys.executable, "-c", save_src, path, "save"],
                        env=env, capture_output=True, text=True, timeout=600)
    assert p1.returncode == 0, p1.stderr[-1500:]

    load_src = _SUBPROC % (4, 2, 2)
    p2 = subprocess.run([sys.executable, "-c", load_src, path, "load"],
                        env=env, capture_output=True, text=True, timeout=600)
    assert p2.returncode == 0, p2.stderr[-1500:]
    out = json.loads(p2.stdout.strip().splitlines()[-1])
    assert out["step"] == 7
    assert np.isfinite(out["loss"])
