"""Launch plane: production mesh construction (512 virtual devices,
subprocess), HLO collective parsing, roofline arithmetic, and what
importing the dry-run module leaves behind."""
from conftest import run_subprocess


MESH_CODE = r"""
import os
assert os.environ["XLA_FLAGS"].endswith("512")
import jax
from repro.launch.mesh import make_production_mesh
m1 = make_production_mesh()
assert m1.shape == {"data": 16, "model": 16}, m1.shape
m2 = make_production_mesh(multi_pod=True)
assert m2.shape == {"pod": 2, "data": 16, "model": 16}
assert m2.size == 512
print("OK")
"""


def test_production_mesh_512():
    assert "OK" in run_subprocess(MESH_CODE, devices=512)


def test_collective_parser_on_real_hlo():
    """Compile a program with known collectives on a virtual mesh and check
    parsed byte counts against hand-computed values."""
    code = r"""
import jax, jax.numpy as jnp
from jax.sharding import PartitionSpec as P, NamedSharding
from repro.launch.hlo_analysis import collective_bytes
from repro.core import compat
mesh = compat.make_mesh((8,), ("x",))

def f(a):
    return jax.lax.psum(a, "x")

fn = compat.shard_map(f, mesh=mesh, in_specs=P("x", None),
                      out_specs=P(None, None))
a = jax.ShapeDtypeStruct((8, 128), jnp.float32,
                         sharding=NamedSharding(mesh, P("x", None)))
comp = jax.jit(fn).lower(a).compile()
cb = collective_bytes(comp.as_text())
assert cb["counts"]["all-reduce"] >= 1, cb
# operand is the [1,128] f32 local shard = 512 bytes per all-reduce
assert cb["bytes"]["all-reduce"] >= 512, cb
print("OK", cb["total_bytes"])
"""
    assert "OK" in run_subprocess(code, devices=8)


def test_roofline_math():
    from repro.launch.hlo_analysis import Roofline

    r = Roofline(
        compute_s=2.0, memory_s=1.0, collective_s=0.5,
        hlo_flops=1e12, hlo_bytes=1e9, coll_bytes=1e8,
        model_flops=4e14, chips=256,
    )
    assert r.dominant == "compute"
    assert r.step_time_bound_s if hasattr(r, "step_time_bound_s") else True
    assert r.step_time_s == 2.0
    assert 0 < r.mfu < 1
    d = r.as_dict()
    assert d["dominant"] == "compute"


DRYRUN_IMPORT_CODE = r"""
import os, sys
before = os.environ["XLA_FLAGS"]
import repro.launch.dryrun
assert os.environ["XLA_FLAGS"] == before, os.environ["XLA_FLAGS"]
gone = ("repro.models", "repro.train", "repro.serve", "repro.configs")
loaded = [m for m in sys.modules if m.split(".")[:2] in
          [g.split(".") for g in gone]]
assert not loaded, loaded
print("OK")
"""


def test_dryrun_import_leaves_xla_flags_alone():
    """Importing ``repro.launch.dryrun`` (for ``compile_mesh_stages``) keeps
    the caller's ``XLA_FLAGS``: only the CLI sets its 512-device count."""
    assert "OK" in run_subprocess(DRYRUN_IMPORT_CODE, devices=8)
