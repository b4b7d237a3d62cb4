import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each runnable cell (skip rules: DESIGN.md section Arch-applicability)
this driver:
  1. builds the production mesh (16x16 single-pod / 2x16x16 multi-pod),
  2. lowers the right step function against ShapeDtypeStruct inputs —
     train_step (fwd+bwd+AdamW) for train_4k, last-token forward for
     prefill_32k, decode_step (one token + cache) for decode_32k/long_500k,
  3. compiles, prints memory_analysis / cost_analysis, parses collective
     bytes from the HLO, derives the three roofline terms,
  4. appends the record to experiments/dryrun.json.

Also lowers the PAPER's own workload ("anotherme": the distributed SSH +
similarity pipeline on the flat 512-executor mesh) so the technique itself
gets a roofline row.

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun                # all cells
  ... --arch qwen1.5-110b --shape train_4k --mesh multi       # one cell
  ... --list                                                  # enumerate
"""
import argparse
import json
import pathlib
import time
import traceback

import jax
import jax.numpy as jnp

from repro.configs import SHAPES, all_archs, get_config, shape_applicable
from repro.configs.base import ModelConfig, ShapeConfig
from repro.launch import hlo_analysis as H
from repro.launch.inputs import input_specs
from repro.launch.mesh import make_executor_mesh, make_production_mesh
from repro.models.model import (
    active_param_count, param_shape_structs, param_shardings,
)

RESULTS = pathlib.Path("experiments")


def _opt_bits(cfg: ModelConfig) -> int:
    from repro.models.model import param_count
    return 8 if param_count(cfg) > 50e9 else 32


def model_flops(cfg: ModelConfig, shape: ShapeConfig) -> float:
    """Analytic MODEL_FLOPS: 6*N_active*D train, 2*N_active*D inference."""
    n = active_param_count(cfg)
    if shape.kind == "train":
        tokens = shape.global_batch * shape.seq_len
        return 6.0 * n * tokens
    if shape.kind == "prefill":
        tokens = shape.global_batch * shape.seq_len
        return 2.0 * n * tokens
    # decode: one token per sequence
    return 2.0 * n * shape.global_batch


def _layer_period(cfg: ModelConfig) -> int:
    """The homogeneous repeat unit (hybrid: a group of `every` layers)."""
    return cfg.shared_attn_every if cfg.family == "hybrid" else 1


def pick_grad_accum(cfg: ModelConfig, shape: ShapeConfig, chips_dp: int) -> int:
    """Grad accumulation so each microbatch holds <=8k tokens per dp shard
    (bounds the scan-carry activation memory; see EXPERIMENTS.md)."""
    per_shard_tokens = shape.global_batch * shape.seq_len // chips_dp
    accum = max(1, per_shard_tokens // 8192)
    while shape.global_batch % (accum * chips_dp) and accum > 1:
        accum //= 2
    return accum


def _params_for(cfg, mesh):
    p_sds = param_shape_structs(cfg)
    p_sh = param_shardings(cfg, mesh)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        p_sds, p_sh,
    )


def _lower_step(cfg, shape, mesh, *, unroll: bool, grad_accum: int,
                with_opt: bool):
    """Lower the cell's step fn for config `cfg` (possibly depth-reduced)."""
    import dataclasses as dc
    p_in = _params_for(cfg, mesh)

    if shape.kind == "train":
        from repro.train.optimizer import OptConfig, init_opt_state
        from repro.train.train_step import TrainConfig, make_train_step
        from repro.models.model import loss_fn

        if with_opt:
            tcfg = TrainConfig(
                opt=OptConfig(state_bits=_opt_bits(cfg)), grad_accum=grad_accum
            )
            step = make_train_step(cfg, tcfg, mesh, unroll=unroll)
            state_sds = jax.eval_shape(
                lambda p: {"opt": init_opt_state(p, tcfg.opt)},
                jax.tree.map(lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), p_in),
            )
            state_sh = _state_shardings(state_sds, param_shardings(cfg, mesh), mesh)
            state_in = jax.tree.map(
                lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                state_sds, state_sh,
            )
            ins = input_specs(cfg, shape, mesh)
            return jax.jit(step, donate_argnums=(0, 1)).lower(p_in, state_in, ins)
        # grad-only probe at microbatch size
        micro = dc.replace(shape, global_batch=shape.global_batch // grad_accum)
        ins = input_specs(cfg, micro, mesh)
        fn = jax.jit(
            jax.grad(
                lambda p, i: loss_fn(p, i, cfg, mesh, unroll=unroll)[0]
            )
        )
        return fn.lower(p_in, ins)
    if shape.kind == "prefill":
        from repro.models.model import forward

        ins = input_specs(cfg, shape, mesh)
        return jax.jit(
            lambda p, i: forward(p, i, cfg, mesh, last_only=True,
                                 unroll=unroll)[0]
        ).lower(p_in, ins)
    # decode
    from repro.serve.kvcache import cache_shape_structs
    from repro.serve.serve_step import make_decode_step
    from repro.models.layers import dp_axes, resolve_spec
    from jax.sharding import NamedSharding

    step = make_decode_step(cfg, mesh, unroll=unroll)
    cache_in = cache_shape_structs(cfg, shape.global_batch, shape.seq_len, mesh)
    tok_shape = (shape.global_batch, 1)
    tok_sh = NamedSharding(
        mesh, resolve_spec(mesh, tok_shape, (dp_axes(mesh), None))
    )
    tok_in = jax.ShapeDtypeStruct(tok_shape, jnp.int32, sharding=tok_sh)
    return jax.jit(step, donate_argnums=(1,)).lower(p_in, cache_in, tok_in)


def _probe_costs(cfg, shape, mesh, grad_accum):
    """Exact per-cell cost reconstruction from shallow UNROLLED lowers.

    XLA's cost_analysis counts while-loop bodies once, so the production
    scan under-reports by ~L.  We lower depth k1 and k2 (in layer periods)
    unrolled; costs are affine in depth: cost(k) = base + k*layer.
    total(L) = base + L*layer, and for train cells the fwd+bwd part is
    multiplied by grad_accum while the optimizer part (probed separately via
    with_opt on depth k1) is counted once.
    """
    import dataclasses as dc

    period = _layer_period(cfg)
    k1, k2 = period, 2 * period
    costs = {}
    for tag, k in (("k1", k1), ("k2", k2)):
        cfg_k = dc.replace(cfg, num_layers=k)
        lowered = _lower_step(cfg_k, shape, mesh, unroll=True,
                              grad_accum=grad_accum, with_opt=False)
        compiled = lowered.compile()
        ca = compiled.cost_analysis()
        coll = H.collective_bytes(compiled.as_text())
        costs[tag] = {
            "flops": float(ca.get("flops", 0.0)),
            "bytes": float(ca.get("bytes accessed", 0.0)),
            "coll": float(coll["total_bytes"]),
            "coll_by_kind": coll["bytes"],
        }

    n_periods = cfg.num_layers // period
    out = {}
    for key in ("flops", "bytes", "coll"):
        layer = costs["k2"][key] - costs["k1"][key]
        base = costs["k1"][key] - layer
        out[key] = base + n_periods * layer
    out["coll_by_kind"] = {
        kind: (costs["k2"]["coll_by_kind"][kind] - costs["k1"]["coll_by_kind"][kind])
        * n_periods
        + 2 * costs["k1"]["coll_by_kind"][kind]
        - costs["k2"]["coll_by_kind"][kind]
        for kind in costs["k1"]["coll_by_kind"]
    }

    if shape.kind == "train":
        # optimizer-only probe: full-depth AdamW update (no loops inside)
        from repro.train.optimizer import OptConfig, adamw_update, init_opt_state

        ocfg = OptConfig(state_bits=_opt_bits(cfg))
        p_in = _params_for(cfg, mesh)
        p_plain = jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype), p_in
        )
        state_sds = jax.eval_shape(lambda p: init_opt_state(p, ocfg), p_plain)
        state_sh = _state_shardings(
            {"opt": state_sds}, param_shardings(cfg, mesh), mesh
        )["opt"]
        state_in = jax.tree.map(
            lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
            state_sds, state_sh,
        )
        g_in = p_in  # grads shaped/sharded like params
        opt_l = jax.jit(
            lambda p, g, s: adamw_update(p, g, s, ocfg), donate_argnums=(0, 2)
        ).lower(p_in, g_in, state_in)
        opt_c = opt_l.compile()
        oca = opt_c.cost_analysis()
        ocoll = H.collective_bytes(opt_c.as_text())
        for key, val in (
            ("flops", float(oca.get("flops", 0.0))),
            ("bytes", float(oca.get("bytes accessed", 0.0))),
            ("coll", float(ocoll["total_bytes"])),
        ):
            out[key] = out[key] * grad_accum + val
        out["grad_accum"] = grad_accum
    return out


def lower_cell(arch: str, shape_name: str, multi_pod: bool):
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = mesh.size
    from repro.models.layers import dp_axes, axis_size
    dp_n = axis_size(mesh, dp_axes(mesh))
    grad_accum = pick_grad_accum(cfg, shape, dp_n) if shape.kind == "train" else 1

    # 1. PRODUCTION compile (scan form) — proves shardability, gives memory
    t0 = time.time()
    lowered = _lower_step(cfg, shape, mesh, unroll=False,
                          grad_accum=grad_accum, with_opt=True)
    compiled = lowered.compile()
    compile_s = time.time() - t0
    print(compiled.memory_analysis())
    print({k: compiled.cost_analysis().get(k) for k in ("flops", "bytes accessed")})

    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "chips": chips, "compile_s": compile_s,
        "grad_accum": grad_accum,
        "memory": H.memory_summary(compiled),
        "status": "ok",
    }

    # 2. cost probes (unrolled shallow) — exact roofline totals.
    # The roofline table is single-pod only (assignment spec); the multi-pod
    # pass proves pod-axis shardability via the production compile above.
    if not multi_pod:
        probe = _probe_costs(cfg, shape, mesh, grad_accum)
        mf = model_flops(cfg, shape)
        roof = H.Roofline(
            compute_s=probe["flops"] / H.PEAK_FLOPS,
            memory_s=probe["bytes"] / H.HBM_BW,
            collective_s=probe["coll"] / H.ICI_BW,
            hlo_flops=probe["flops"], hlo_bytes=probe["bytes"],
            coll_bytes=probe["coll"], model_flops=mf, chips=chips,
        )
        rec["collectives_by_kind"] = probe.get("coll_by_kind")
        rec["roofline"] = roof.as_dict()
    return rec


def _state_shardings(state_sds, p_sh, mesh):
    """Opt-state shardings: moments inherit the parameter sharding; int8
    block scales drop the (blocked) last-dim partitioning; step replicated."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    rep = NamedSharding(mesh, P())

    def build(sds, sh):
        # sds mirrors {"opt": {"step":..., "moments": <tree like params>}}
        out = {"step": rep, "moments": {}}

        def walk(m_sds, p_sharding):
            if isinstance(m_sds, dict) and "m" in m_sds and "v" in m_sds:
                def one(x):
                    if isinstance(x, dict):  # int8 {"q","scale"}: the block
                        # scales drop the (blocked) last-dim partitioning
                        spec = p_sharding.spec
                        return {
                            "q": p_sharding,
                            "scale": NamedSharding(mesh, P(*spec[:-1], None))
                            if len(spec) > 0 else rep,
                        }
                    return p_sharding
                return {"m": one(m_sds["m"]), "v": one(m_sds["v"])}
            return {
                k: walk(m_sds[k], p_sharding[k]) for k in m_sds
            }

        out["moments"] = walk(sds["opt"]["moments"], sh)
        return {"opt": out}

    return build(state_sds, p_sh)


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


def enumerate_cells():
    cells = []
    for arch in all_archs():
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            ok, why = shape_applicable(cfg, shape)
            cells.append((arch, sname, ok, why))
    return cells


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--list", action="store_true")
    ap.add_argument("--anotherme", action="store_true")
    ap.add_argument("--out", default="experiments/dryrun.json")
    args = ap.parse_args()

    cells = enumerate_cells()
    if args.list:
        for arch, sname, ok, why in cells:
            print(f"{arch:20s} {sname:12s} {'RUN' if ok else 'SKIP: ' + why}")
        return

    out_path = pathlib.Path(args.out)
    out_path.parent.mkdir(parents=True, exist_ok=True)
    records = []
    if out_path.exists():
        records = json.loads(out_path.read_text())

    def done(arch, shape, mesh):
        return any(
            r["arch"] == arch and r["shape"] == shape and r["mesh"] == mesh
            and r["status"] == "ok"
            for r in records
        )

    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    if args.anotherme:
        for mp in meshes:
            rec = lower_anotherme(mp)
            records.append(rec)
            out_path.write_text(json.dumps(records, indent=1))
            print(json.dumps(rec["roofline"], indent=1))
        return

    for arch, sname, ok, why in cells:
        if args.arch and arch != args.arch:
            continue
        if args.shape and sname != args.shape:
            continue
        if not ok:
            print(f"SKIP {arch} {sname}: {why}")
            continue
        for mp in meshes:
            mname = "2x16x16" if mp else "16x16"
            if done(arch, sname, mname):
                print(f"CACHED {arch} {sname} {mname}")
                continue
            print(f"=== {arch} {sname} {mname} ===", flush=True)
            try:
                rec = lower_cell(arch, sname, mp)
            except Exception as e:
                traceback.print_exc()
                rec = {
                    "arch": arch, "shape": sname, "mesh": mname,
                    "status": f"error: {type(e).__name__}: {str(e)[:500]}",
                }
            records.append(rec)
            out_path.write_text(json.dumps(records, indent=1))
            if rec["status"] == "ok" and "roofline" in rec:
                print(json.dumps(rec["roofline"], indent=1), flush=True)


if __name__ == "__main__":
    main()
