"""Dry-run of the paper's workload at production scale: lower and compile
the engine's staged one-shot programs (``MeshStages``: route, join, score)
for 10^6 trajectories on a flat executor mesh of 256 or 512 virtual host
devices, print each program's memory analysis, derive the roofline terms
from the compiled cost and collective bytes, and append the record to
``experiments/dryrun.json``.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun [--mesh single|multi|both]
"""
import argparse
import json
import os
import pathlib
import time

import jax
import jax.numpy as jnp

from repro.launch import hlo_analysis as H
from repro.launch.mesh import make_executor_mesh


def compile_mesh_stages(mesh, plan, key_fn, *, L: int, num_places: int = 10_000,
                        lcs_impl: str = "wavefront") -> dict:
    """The engine's staged one-shot programs (:class:`repro.api.MeshStages`:
    route, join, score) compiled for a hand-set ``plan`` over ``mesh``,
    each lowered with the shapes the stage before returns:
    ``{name: compiled}``.  Nothing runs, so no data pass plans the job."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.api import MeshStages
    from repro.core.similarity import default_betas

    stages = MeshStages(
        mesh, n_shards=plan.n_shards, local_n=plan.local_n,
        betas=default_betas(3), key_fn=key_fn, axis_name="ex",
        score_mode="replicate", lcs_impl=lcs_impl, score_prune=False,
        prune_tau=0.0, subtraj=None,
    )
    rows = NamedSharding(mesh, P("ex", None))
    flat = NamedSharding(mesh, P("ex"))
    n = plan.n_shards * plan.local_n
    places = jax.ShapeDtypeStruct((n, L), jnp.int32, sharding=rows)
    lengths = jax.ShapeDtypeStruct((n,), jnp.int32, sharding=flat)
    # the semantic forest (replicated; encoding runs in-mesh from it —
    # the [N, 3, L] code table never exists as a program input)
    tables = jax.ShapeDtypeStruct((3, num_places), jnp.int32,
                                  sharding=NamedSharding(mesh, P()))

    def sharded(outs):
        return [jax.ShapeDtypeStruct(x.shape, x.dtype, sharding=flat)
                for x in outs]

    route = stages.route(plan.shingle_route_cap)
    join = stages.join(plan.local_pair_cap, plan.pair_route_cap)
    score = stages.score(plan)
    rk, rid, _ = sharded(jax.eval_shape(route, places, places, lengths,
                                        tables))
    left, right, _ = sharded(jax.eval_shape(join, rk, rid, lengths))
    return {
        "route": route.lower(places, places, lengths, tables).compile(),
        "join": join.lower(rk, rid, lengths).compile(),
        "score": score.lower(left, right, places, lengths, tables).compile(),
    }


def stages_costs(compiled: dict) -> dict:
    """Per-device flops, bytes and collective bytes of staged programs run
    one after the other (summed), and the largest program's memory."""
    flops = bytes_ = 0.0
    coll = {"bytes": {}, "total_bytes": 0}
    for c in compiled.values():
        ca = c.cost_analysis()
        flops += float(ca.get("flops", 0.0))
        bytes_ += float(ca.get("bytes accessed", 0.0))
        cb = H.collective_bytes(c.as_text())
        for k, v in cb["bytes"].items():
            coll["bytes"][k] = coll["bytes"].get(k, 0) + v
        coll["total_bytes"] += cb["total_bytes"]
    mems = [H.memory_summary(c) for c in compiled.values()]
    memory = max(mems, key=lambda m: m["peak_bytes_est"])
    return {"flops": flops, "bytes": bytes_, "collectives": coll,
            "memory": memory}


def lower_anotherme(multi_pod: bool, n_traj: int = 1_048_576, L: int = 16):
    """The paper's own workload on the flat executor mesh (512 devices).

    Compiles the engine's staged programs with a capacity plan hand-set
    for the 1M-trajectory shape (no data pass); the "ssh" registry
    backend supplies the on-device key_fn.
    """
    from repro.api import BackendContext, DistributedPlan, get_backend

    mesh = make_executor_mesh(512 if multi_pod else 256)
    n_shards = mesh.size
    local_n = n_traj // n_shards
    S = 560  # C(16,3)
    plan = DistributedPlan(
        n_shards=n_shards, local_n=local_n,
        shingle_route_cap=int(local_n * S / n_shards * 1.3) + 64,
        local_pair_cap=1 << 18, pair_route_cap=1 << 12, scored_cap=1 << 18,
    )
    key_fn = get_backend("ssh").shard_key_fn(
        BackendContext(k=3, num_types=300))
    t0 = time.time()
    compiled = compile_mesh_stages(mesh, plan, key_fn, L=L)
    compile_s = time.time() - t0
    costs = stages_costs(compiled)
    for name, c in compiled.items():
        print(name, c.memory_analysis())
    roof = H.Roofline(
        compute_s=costs["flops"] / H.PEAK_FLOPS,
        memory_s=costs["bytes"] / H.HBM_BW,
        collective_s=costs["collectives"]["total_bytes"] / H.ICI_BW,
        hlo_flops=costs["flops"], hlo_bytes=costs["bytes"],
        coll_bytes=float(costs["collectives"]["total_bytes"]),
        model_flops=0.0, chips=n_shards,
    )
    return {
        "arch": "anotherme-1M", "shape": f"N={n_traj},L={L}",
        "mesh": f"ex{n_shards}", "chips": n_shards,
        "compile_s": compile_s,
        "memory": costs["memory"],
        "collectives": costs["collectives"],
        "roofline": roof.as_dict(),
        "status": "ok",
    }


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--out", default="experiments/dryrun.json")
    args = ap.parse_args()
    # the executor mesh needs 512 host devices; the count binds when jax
    # first touches a device, which nothing has done yet
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    records = []
    if out_path.exists():
        records = json.loads(out_path.read_text())
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]
    for mp in meshes:
        rec = lower_anotherme(mp)
        records.append(rec)
        out_path.write_text(json.dumps(records, indent=1))
        print(json.dumps(rec["roofline"], indent=1))


if __name__ == "__main__":
    main()
