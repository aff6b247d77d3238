"""launch/mesh.py unit tests: production mesh geometry + emulated meshes.

The production builders need 256/512 devices, and jax pins the device
count at first init — so those run in a subprocess with the dry-run's
``XLA_FLAGS`` trick. The emulated-mesh API and error paths run in
process with however many devices the suite sees.
"""
import os
import subprocess
import sys
import textwrap

import pytest

import jax

from repro.launch.mesh import dp_axes, dp_degree, make_emulated_mesh


def test_dp_axes():
    assert dp_axes(False) == ("data",)
    assert dp_axes(True) == ("pod", "data")


def test_emulated_mesh_axes_and_degree():
    mesh = make_emulated_mesh(1, 1)
    assert mesh.axis_names == ("data", "model")
    assert mesh.shape["data"] == 1 and mesh.shape["model"] == 1
    assert dp_degree(mesh, multi_pod=False) == 1


def test_emulated_mesh_uses_device_budget():
    n = jax.device_count()
    mesh = make_emulated_mesh(n, 1)
    assert mesh.size == n
    assert dp_degree(mesh, multi_pod=False) == n


def test_emulated_mesh_too_large_names_the_fix():
    with pytest.raises(ValueError,
                       match="xla_force_host_platform_device_count"):
        make_emulated_mesh(jax.device_count() + 1, 2)


def test_executor_refuses_another_sync_before_building_a_mesh():
    """``MeshExecutor`` has one gradient-sync spelling; naming another
    fails at once, before a mesh is asked for (so on one device too)."""
    from repro.configs import smoke_config
    from repro.exec import MeshExecutor

    with pytest.raises(ValueError, match="sync must be 'shard_map'"):
        MeshExecutor(smoke_config("qwen2.5-3b"), sync="named_sharding",
                     n_groups=4, redundancy=2)


def test_production_mesh_geometry_subprocess():
    """Real ``make_production_mesh`` construction at 512 forced host
    devices: shapes, axis names, and DP degrees of both launch targets.
    """
    prog = textwrap.dedent("""
        import os
        os.environ["XLA_FLAGS"] = \
            "--xla_force_host_platform_device_count=512"
        from repro.launch.mesh import (dp_axes, dp_degree,
                                       make_production_mesh)

        single = make_production_mesh()
        assert single.axis_names == ("data", "model")
        assert dict(single.shape) == {"data": 16, "model": 16}
        assert single.size == 256
        assert dp_degree(single, multi_pod=False) == 16

        multi = make_production_mesh(multi_pod=True)
        assert multi.axis_names == ("pod", "data", "model")
        assert dict(multi.shape) == {"pod": 2, "data": 16, "model": 16}
        assert multi.size == 512
        assert dp_degree(multi, multi_pod=True) == 32
        print("MESH-GEOMETRY-OK")
    """)
    env = dict(os.environ)
    env["PYTHONPATH"] = (os.path.join(os.path.dirname(__file__), os.pardir,
                                      "src")
                         + os.pathsep + env.get("PYTHONPATH", ""))
    env.pop("XLA_FLAGS", None)
    env.setdefault("JAX_PLATFORMS", "cpu")
    out = subprocess.run([sys.executable, "-c", prog], env=env,
                         capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stderr
    assert "MESH-GEOMETRY-OK" in out.stdout
