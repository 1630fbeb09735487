"""Sharding rules + a real multi-device lowering (subprocess: the fake
device count must be set before jax initialises)."""
import json
import subprocess
import sys
import textwrap

import pytest

from repro.config.registry import get_config, list_archs

_CHILD = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import json
    import jax
    import jax.numpy as jnp
    from jax.sharding import PartitionSpec as P
    from repro.config.base import ShapeConfig
    from repro.config.registry import get_config
    from repro.launch import specs as specs_lib

    from repro.launch.mesh import make_host_mesh
    mesh = make_host_mesh(data=2, model=4)
    results = {}
    for arch in ["smollm-135m", "qwen3-moe-235b-a22b", "mamba2-130m",
                 "zamba2-1.2b"]:
        cfg = get_config(arch).reduced()
        shape = ShapeConfig("t", seq_len=64, global_batch=4, kind="train")
        fn, args, in_sh, out_sh = specs_lib.build(cfg, shape, mesh)
        compiled = jax.jit(fn, in_shardings=in_sh,
                           out_shardings=out_sh).lower(*args).compile()
        ca = compiled.cost_analysis()
        if isinstance(ca, (list, tuple)):  # older jax: one dict per device
            ca = ca[0]
        results[arch] = float(ca.get("flops", 0))
    print(json.dumps(results))
""")


def test_param_specs_respect_divisibility():
    import jax
    from jax.sharding import PartitionSpec as P
    # build spec decisions without touching real devices: fake mesh object
    class FakeMesh:
        axis_names = ("data", "model")
        class devices:
            shape = (16, 16)
    from repro.launch import specs as specs_lib
    from repro.sharding import rules
    for arch in list_archs():
        cfg = get_config(arch)
        p_shape = specs_lib.params_shape(cfg)
        specs = rules.param_specs(cfg, p_shape, FakeMesh)
        flat = jax.tree_util.tree_flatten_with_path(
            specs, is_leaf=lambda x: isinstance(x, P))[0]
        shapes = jax.tree_util.tree_flatten_with_path(p_shape)[0]
        for (path, spec), (_, leaf) in zip(flat, shapes):
            used = set()
            for dim, ax in enumerate(spec):
                if ax is None:
                    continue
                axes = ax if isinstance(ax, tuple) else (ax,)
                for a in axes:
                    assert a not in used, (arch, path, spec)
                    used.add(a)
                    assert leaf.shape[dim] % 16 == 0, (arch, path, spec,
                                                       leaf.shape)


@pytest.mark.slow
def test_multidevice_train_step_lowers():
    out = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-2000:]
    flops = json.loads(out.stdout.strip().splitlines()[-1])
    assert all(v > 0 for v in flops.values())
