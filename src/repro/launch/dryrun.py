"""Multi-pod dry-run — deliverable (e).

For every (architecture × input shape × mesh) combination:
  jit(step).lower(*ShapeDtypeStructs).compile()
on the production meshes (16,16) and (2,16,16), printing
``compiled.memory_analysis()`` (fits?) and ``compiled.cost_analysis()``
(FLOPs/bytes for §Roofline), plus the collective-bytes breakdown parsed
from the post-SPMD HLO (ICI vs inter-pod DCN).

Results are cached as JSON under ``experiments/dryrun/`` (one file per
combo) so interrupted sweeps resume. Usage:

  PYTHONPATH=src python -m repro.launch.dryrun                 # everything
  PYTHONPATH=src python -m repro.launch.dryrun --arch granite-34b \
      --shape train_4k --mesh multi --boundary striped

``main`` asks for 512 host devices through ``XLA_FLAGS`` before the
backend starts, adding the flag to any ``XLA_FLAGS`` already set; merely
importing this module changes nothing.
"""
import argparse
import json
import os
import re
import time
import traceback
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import ARCHS, canon
from repro.launch import shapes as shp
from repro.launch.mesh import auto_mesh, make_production_mesh
from repro.models.transformer import build_model
from repro.optim.optimizer import OptimizerConfig, init_opt_state, make_train_step
from repro.parallel.pipeline import make_pipeline_loss
from repro.parallel.sharding import make_param_shardings

OUT_DIR = os.path.join(os.path.dirname(__file__), "..", "..", "..", "experiments", "dryrun")

# TPU v5e constants (§Roofline)
PEAK_FLOPS = 197e12  # bf16 / chip
HBM_BW = 819e9  # bytes/s / chip
ICI_BW = 50e9  # bytes/s / link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_COLLECTIVES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
)


def _shape_bytes(type_str: str) -> int:
    """'bf16[16,128,8]' -> byte size."""
    m = re.match(r"([a-z0-9]+)\[([0-9,]*)\]", type_str)
    if not m:
        return 0
    dt, dims = m.groups()
    b = _DTYPE_BYTES.get(dt, 4)
    n = 1
    for d in dims.split(","):
        if d:
            n *= int(d)
    return n * b


def _replica_groups(line: str):
    """A collective's device groups in either of HLO's forms: explicit,
    ``{{0,2},{1,3}}``, or iota, ``[2,2]<=[2,2]T(1,0)`` (the ids of the
    shape in brackets, transposed by ``T``, read as groups by rows)."""
    m = re.search(r"replica_groups=\{((?:\{[0-9,]*\},?)*)\}", line)
    if m:
        return [[int(x) for x in g.split(",") if x]
                for g in re.findall(r"\{([0-9,]*)\}", m.group(1))]
    m = re.search(r"replica_groups=\[(\d+),(\d+)\]<=\[([0-9,]+)\](?:T\(([0-9,]+)\))?", line)
    if not m:
        return []
    ids = np.arange(int(m[1]) * int(m[2])).reshape([int(d) for d in m[3].split(",")])
    if m[4]:
        ids = ids.transpose([int(d) for d in m[4].split(",")])
    return ids.reshape(int(m[1]), int(m[2])).tolist()


def _crosses_pod(line: str, pod_stride: int) -> bool:
    """True if the collective's device groups, or its source-target pairs,
    span pods (device id // stride differs). Device order is pod-major."""
    if "source_target_pairs=" in line:
        pairs = re.findall(r"\{(\d+),(\d+)\}", line.split("source_target_pairs=")[1])
        return any(int(a) // pod_stride != int(b) // pod_stride for a, b in pairs)
    return any(len({i // pod_stride for i in g}) > 1 for g in _replica_groups(line))


def collective_bytes(hlo_text: str, pod_stride: int) -> Dict[str, float]:
    """Sum per-device collective operand bytes from post-SPMD HLO."""
    out = {"ici": 0.0, "dcn": 0.0, "by_op": {}}
    for line in hlo_text.splitlines():
        ls = line.strip()
        m = re.match(r"%?[\w.\-]+ = (.+?) (all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)(-start)?\(", ls)
        if not m:
            continue
        result_type, op = m.group(1), m.group(2)
        shapes = re.findall(r"[a-z0-9]+\[[0-9,]*\]", result_type)
        nbytes = sum(_shape_bytes(s) for s in shapes)
        if op == "all-reduce":
            nbytes *= 2  # ring: reduce-scatter + all-gather volume
        cross = pod_stride > 0 and _crosses_pod(ls, pod_stride)
        key = "dcn" if cross else "ici"
        out[key] += nbytes
        out["by_op"][op] = out["by_op"].get(op, 0.0) + nbytes
    return out


def _sds_params(model, mesh, fsdp: bool = False, stages: bool = True):
    p_shapes = jax.eval_shape(model.init, jax.random.PRNGKey(0))
    shardings = make_param_shardings(p_shapes, mesh, fsdp=fsdp, stages=stages)
    return jax.tree.map(
        lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
        p_shapes,
        shardings,
    )


def head_aligned_tp(cfg, max_tp: int = 16) -> int:
    """Largest TP degree ≤ max_tp that lands on attention-head boundaries
    (§Perf C: splitting inside head_dim adds a psum to every attention
    einsum — 5.7× on minitron's prefill collective term)."""
    tp = max_tp
    while tp > 1:
        if cfg.num_heads % tp == 0 and (
            cfg.num_kv_heads % tp == 0 or cfg.num_kv_heads == 1
        ):
            return tp
        tp //= 2
    return 1


def build_lowerable(arch: str, shape: str, multi_pod: bool, boundary: str = "striped",
                    n_micro: int = 4, fsdp: Optional[bool] = None,
                    relayout: bool = False):
    """Returns (fn, example_args) ready for jit(...).lower(*args).

    fsdp defaults to True for train shapes (§Perf B: f32 params + Adam on
    a model-axis-only layout need 35 GB/device for the 33B archs; 2D
    sharding brings granite to 3.3 GB at +0.24 s of weight all-gathers).

    relayout=True re-lays the same 256-chip pod as (256/tp, tp) with a
    head-aligned tp (§Perf C); single-pod only.
    """
    cfg = shp.config_for(arch, shape)
    if fsdp is None:
        fsdp = shp.SHAPES[shape]["kind"] == "train"
    model = build_model(cfg)
    if relayout and not multi_pod:
        tp = head_aligned_tp(cfg)
        mesh = auto_mesh((256 // tp, tp), ("data", "model"))
    else:
        mesh = make_production_mesh(multi_pod=multi_pod)
    kind = shp.SHAPES[shape]["kind"]
    opt_cfg = OptimizerConfig()

    with jax.set_mesh(mesh):
        # pods are pipeline stages in training, replicas in serving
        params_sds = _sds_params(model, mesh, fsdp=fsdp, stages=kind == "train")
        if kind == "train":
            if multi_pod:
                loss_fn = make_pipeline_loss(cfg, mesh, n_micro=n_micro, boundary=boundary,
                                             fsdp=fsdp)
                step = make_train_step(loss_fn, opt_cfg, loss_has_metrics=False)
            else:
                step = make_train_step(model.loss, opt_cfg)
            opt_sds = jax.eval_shape(init_opt_state, params_sds)
            # opt state shards like its params; step counter replicated
            p_shard = jax.tree.map(lambda s: s.sharding, params_sds)
            opt_sds = type(opt_sds)(
                step=jax.ShapeDtypeStruct((), jnp.int32, sharding=NamedSharding(mesh, P())),
                mu=jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                    opt_sds.mu, p_shard),
                nu=jax.tree.map(
                    lambda s, sh: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sh),
                    opt_sds.nu, p_shard),
            )
            batch_sds = shp.batch_specs(cfg, shape, mesh, multi_pod=multi_pod,
                                        pipeline=multi_pod)
            fn = jax.jit(step, donate_argnums=(0, 1))
            args = (params_sds, opt_sds, batch_sds)
        elif kind == "prefill":
            batch_sds = shp.batch_specs(cfg, shape, mesh, multi_pod=multi_pod)
            if cfg.family == "audio":
                fn = jax.jit(model.loss)  # encoder forward (+ loss head)
                args = (params_sds, batch_sds)
            else:
                cache_sds = shp.cache_specs(cfg, shape, mesh, model, multi_pod=multi_pod)
                fn = jax.jit(model.prefill, donate_argnums=(2,))
                args = (params_sds, batch_sds, cache_sds)
        else:  # decode
            batch_sds = shp.batch_specs(cfg, shape, mesh, multi_pod=multi_pod)
            cache_sds = shp.cache_specs(cfg, shape, mesh, model, multi_pod=multi_pod)
            fn = jax.jit(model.decode_step, donate_argnums=(1,))
            args = (params_sds, cache_sds, batch_sds["tokens"], batch_sds["pos"])
        return mesh, fn, args, cfg


def wan_projection(dcn_bytes: float, topo,
                   drift: Optional[str] = None,
                   fleet_jobs: int = 0,
                   fail: Optional[str] = None,
                   tracer=None,
                   trace_label: Optional[str] = None) -> Dict[str, Any]:
    """Project the measured inter-pod DCN bytes onto a WAN topology: the
    per-iteration transfer time if the pod boundary ran over the given
    (possibly heterogeneous) WAN instead of the datacenter DCN.  Uses the
    bottleneck pair — the paper's placement rule puts the cut on the best
    pair, but capacity planning must survive the worst.

    ``drift="outage"`` adds the reactive-control-plane projection: the
    boundary transfer priced through a sustained 10x degradation of the
    pair it rides (what a static plan keeps paying) vs. re-routed onto
    the best alternative pair (what ``repro.core.control`` migrates to
    once the drift detector fires).

    ``fleet_jobs=N`` (N ≥ 2) adds the multi-job sharing projection
    (``repro.core.fleet``): N jobs' boundary transfers contending for
    the same pair.  Contention-aware temporal sharing serializes them —
    job k's transfer completes at k·S, mean (N+1)/2·S — while the naive
    always-fair-share model runs every transfer at 1/N rate so *all* of
    them complete at N·S.

    ``fail="dc@t"`` (e.g. ``"us-west@600"``, seconds) adds the failure &
    elasticity projection (``repro.core.failures``): that DC suffers an
    unplanned outage at t, its pairs drop to residual bandwidth, and the
    boundary transfer is priced three ways — keep riding the dead DC at
    residual rate (static), haul the live state off it over the same
    residual links (ship), or pull the last async checkpoint between
    healthy DCs at full rate (checkpoint-aware restore).

    ``tracer`` (``repro.obs.Tracer``) additionally *simulates* one
    iteration of a pipeline whose boundary transfers carry the measured
    DCN bytes over this WAN, recording GPU and channel spans under the
    ``trace_label`` lane group — the closed-form projections above as an
    inspectable Perfetto timeline (exported by ``--trace``)."""
    from repro.core import wan as _wan
    from repro.core.topology import TopologyMatrix

    if isinstance(topo, str):
        from repro.core.topology import preset

        topo = preset(topo)
    worst = topo.bottleneck()
    best = topo.best_link()
    out = {
        "topology": topo.name,
        "worst_pair_s": worst.transfer_ms(dcn_bytes) / 1e3,
        "best_pair_s": best.transfer_ms(dcn_bytes) / 1e3,
        "worst_pair_gbps": worst.bw_gbps,
        "best_pair_gbps": best.bw_gbps,
    }
    if drift == "outage":
        deg = _wan.BandwidthSchedule.outage(
            best.bw_gbps, 1e-3, 1e15, best.bw_gbps / 10.0)
        static_s = (deg.transfer_ms(dcn_bytes, 1.0)
                    + best.latency_ms) / 1e3
        # the re-plan routes the cut onto the best *alternative* pair —
        # a different physical pair, not the reverse direction of the
        # degraded one (wan_pairs() yields both directions)
        by_pair = {}
        for a, b in topo.wan_pairs():
            by_pair.setdefault(frozenset((a, b)), []).append(topo.link(a, b))
        ranked = sorted(
            ((max(ls, key=lambda l: (l.bw_gbps, -l.latency_ms)), key)
             for key, ls in by_pair.items()),
            key=lambda kl: (-kl[0].bw_gbps, kl[0].latency_ms))
        if len(ranked) > 1:
            reactive_s = ranked[1][0].transfer_ms(dcn_bytes) / 1e3
        else:
            reactive_s = static_s  # single-pair WAN: nowhere to migrate
        out["drift"] = {
            "scenario": "10x outage on the boundary pair",
            "static_s": static_s,  # the plan keeps riding the degraded pair
            "reactive_s": reactive_s,  # re-planned onto the best alternative
            "reactive_speedup": static_s / reactive_s if reactive_s else None,
        }
    if fleet_jobs >= 2:
        n = fleet_jobs
        per_job_s = best.transfer_ms(dcn_bytes) / 1e3
        out["fleet"] = {
            "scenario": f"{n} jobs sharing the boundary pair",
            "per_job_s": per_job_s,  # one transfer alone at full rate
            # temporal sharing: transfers serialize — the k-th completes
            # at k·S; mean job waits (N+1)/2·S, the last N·S
            "temporal_mean_s": (n + 1) / 2.0 * per_job_s,
            "temporal_worst_s": n * per_job_s,
            # naive always-fair-share: every transfer at 1/N rate, all
            # complete together at N·S — no job ever finishes early
            "fair_share_mean_s": n * per_job_s,
            "temporal_mean_speedup": 2.0 * n / (n + 1),
        }
    if fail:
        from repro.core.failures import FailureEvent, FailureTrace

        if "@" not in fail:
            raise ValueError(f"--fail wants dc@t_seconds, got {fail!r}")
        dc, t_str = fail.rsplit("@", 1)
        if dc not in topo.dc_names:
            raise ValueError(f"--fail DC {dc!r} not in {topo.dc_names}")
        at_ms = float(t_str) * 1e3
        residual = 0.05
        trace = FailureTrace(events=(
            FailureEvent(at_ms=at_ms, kind="dc_outage", dc=dc,
                         residual_frac=residual),))
        degraded = trace.apply_to_topology(topo)
        idx = topo.index_of(dc)
        dead_pairs = [(a, b) for a, b in topo.wan_pairs() if idx in (a, b)]
        alive = [topo.link(a, b) for a, b in topo.wan_pairs()
                 if idx not in (a, b)]
        # the boundary transfer through the dead DC, at residual rate
        residual_s = max(
            degraded.bandwidth_schedule(a, b).transfer_ms(
                dcn_bytes, at_ms + 1.0) / 1e3 + topo.link(a, b).latency_ms / 1e3
            for a, b in dead_pairs)
        # restore: the checkpoint lives on healthy DCs — full-rate pull
        restore_s = (min(l.transfer_ms(dcn_bytes) for l in alive) / 1e3
                     if alive else residual_s)
        out["failure"] = {
            "scenario": f"{dc} dies at t={at_ms/1e3:.0f}s "
                        f"(residual {residual:.0%})",
            "dead_dc": dc,
            "at_s": at_ms / 1e3,
            # a static plan keeps paying the residual rate every iteration
            "static_s": residual_s,
            # shipping live state off the corpse rides the same residual
            # links once — then runs free of the dead DC
            "ship_once_s": residual_s,
            # checkpoint-aware restore never touches the dead DC
            "restore_s": restore_s,
            "restore_speedup": residual_s / restore_s if restore_s else None,
        }
    if tracer is not None and getattr(tracer, "enabled", False):
        import dataclasses as _dc

        from repro.core.control import plan_spec
        from repro.core.dc_selection import JobModel, algorithm1, best_plan
        from repro.core.simulator import simulate as _simulate

        sim_topo = topo
        if not sim_topo.dc_names:
            sim_topo = _dc.replace(
                topo, dc_names=tuple(f"dc{i}" for i in range(topo.n_dcs)))
        # one microbatch's boundary activation carries an even share of
        # the measured per-step DCN bytes; a nominal 10 ms compute keeps
        # the bubbles visible next to the WAN transfers
        m = 8
        proj_job = JobModel(
            t_fwd_ms=10.0, act_bytes=max(dcn_bytes, 1.0) / m,
            partition_param_bytes=2e8, microbatches=m, topology=sim_topo)
        plan = best_plan(algorithm1(
            proj_job, {d: 8 for d in sim_topo.dc_names}, P=8, C=1))
        res = _simulate(plan_spec(proj_job, plan, sim_topo), sim_topo,
                        validate=True, tracer=tracer,
                        trace_label=trace_label or "wanproj")
        out["trace"] = {
            "label": trace_label or "wanproj",
            "iteration_ms": res.iteration_ms,
            "dc_order": [d for d in plan.dc_order
                         if plan.partitions.get(d, 0)],
        }
    return out


def run_one(arch: str, shape: str, mesh_name: str, boundary: str = "striped",
            fsdp: Optional[bool] = None, relayout: bool = False,
            wan_preset: Optional[str] = None,
            wan_drift: Optional[str] = None,
            wan_fleet: int = 0,
            wan_fail: Optional[str] = None,
            tracer=None, trace_label: Optional[str] = None) -> Dict[str, Any]:
    multi_pod = mesh_name == "multi"
    ok, why = shp.shape_supported(arch, shape)
    if not ok:
        return {"arch": arch, "shape": shape, "mesh": mesh_name, "status": "skipped",
                "reason": why}
    t0 = time.time()
    mesh, fn, args, cfg = build_lowerable(arch, shape, multi_pod, boundary,
                                          fsdp=fsdp, relayout=relayout)
    with jax.set_mesh(mesh):
        lowered = fn.lower(*args)
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

        try:
            mem = compiled.memory_analysis()
            mem_d = {
                "bytes_per_device": getattr(mem, "temp_size_in_bytes", None),
                "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
                "output_bytes": getattr(mem, "output_size_in_bytes", None),
                "peak_bytes": getattr(mem, "peak_memory_in_bytes", None),
                "generated_code_bytes": getattr(mem, "generated_code_size_in_bytes", None),
            }
        except Exception as e:  # CPU backend may not support it
            mem_d = {"error": str(e)}
        try:
            cost = compiled.cost_analysis()
            cost_d = {k: float(v) for k, v in cost.items()
                      if isinstance(v, (int, float)) and k in
                      ("flops", "bytes accessed", "transcendentals", "optimal_seconds")}
        except Exception as e:
            cost_d = {"error": str(e)}

        pod_stride = 256 if multi_pod else 0
        hlo = compiled.as_text()
        coll = collective_bytes(hlo, pod_stride)

    chips = 512 if multi_pod else 256
    s = shp.SHAPES[shape]
    tokens = s["global_batch"] * (s["seq_len"] if s["kind"] != "decode" else 1)
    n_active = cfg.active_param_count()
    model_flops_global = (6.0 if s["kind"] == "train" else 2.0) * n_active * tokens
    model_flops_dev = model_flops_global / chips
    flops_dev = cost_d.get("flops", float("nan"))
    # NOTE: on the CPU backend, XLA's cost analysis does NOT multiply a
    # while-loop (lax.scan) body by its trip count, so `flops` undercounts
    # by roughly the layer count.  The compute term therefore uses the
    # analytic MODEL_FLOPS; the raw HLO figure is kept as compute_s_hlo.
    result = {
        "arch": arch, "shape": shape, "mesh": mesh_name, "boundary": boundary,
        "status": "ok",
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "memory": mem_d, "cost": cost_d,
        "collectives": coll,
        "roofline": {
            "compute_s": model_flops_dev / PEAK_FLOPS,
            "compute_s_hlo": flops_dev / PEAK_FLOPS if flops_dev == flops_dev else None,
            "memory_s": cost_d.get("bytes accessed", float("nan")) / HBM_BW,
            "collective_s": (coll["ici"] + coll["dcn"]) / ICI_BW,
            "dcn_bytes": coll["dcn"],
            "model_flops_per_device": model_flops_dev,
            # scan-body undercount caveat applies; >1 means the per-trip
            # HLO flops are below the analytic per-layer work
            "useful_flops_ratio": model_flops_dev / flops_dev
            if flops_dev and flops_dev == flops_dev else None,
        },
        "params": cfg.param_count(),
        "active_params": n_active,
    }
    if wan_preset:
        result["wan"] = wan_projection(coll["dcn"], wan_preset, drift=wan_drift,
                                       fleet_jobs=wan_fleet, fail=wan_fail,
                                       tracer=tracer, trace_label=trace_label)
    return result


HOST_DEVICES_FLAG = "--xla_force_host_platform_device_count"


def _request_host_devices(n: int = 512) -> None:
    """Ask the CPU backend for ``n`` devices; must run before it starts."""
    flags = os.environ.get("XLA_FLAGS", "")
    if HOST_DEVICES_FLAG not in flags:
        os.environ["XLA_FLAGS"] = f"{flags} {HOST_DEVICES_FLAG}={n}".strip()


def main():
    _request_host_devices()
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None, choices=list(shp.SHAPES) + [None])
    ap.add_argument("--mesh", default=None, choices=["single", "multi", None])
    ap.add_argument("--boundary", default="striped", choices=["striped", "direct"])
    ap.add_argument("--no-fsdp", action="store_true",
                    help="paper-faithful model-axis-only param sharding")
    ap.add_argument("--relayout", action="store_true",
                    help="head-aligned single-pod mesh re-layout (§Perf C)")
    ap.add_argument("--wan-preset", default=None,
                    choices=["azure", "skewed", "star", "chain"],
                    help="also project the inter-pod DCN bytes onto this "
                         "WAN topology (repro.core.topology presets)")
    ap.add_argument("--wan-drift", default=None, choices=["outage"],
                    help="with --wan-preset: add the reactive control-plane "
                         "projection (static plan riding a 10x-degraded "
                         "boundary pair vs re-planned onto the best "
                         "alternative — repro.core.control)")
    ap.add_argument("--fleet", type=int, default=0, metavar="N",
                    help="with --wan-preset: add the multi-job sharing "
                         "projection — N jobs' boundary transfers on one "
                         "pair, contention-aware temporal sharing vs naive "
                         "always-fair-share (repro.core.fleet)")
    ap.add_argument("--fail", default=None, metavar="DC@T",
                    help="with --wan-preset: add the failure & elasticity "
                         "projection — that DC dies at T seconds, boundary "
                         "transfer priced static vs ship-live vs "
                         "checkpoint-aware restore (repro.core.failures); "
                         "e.g. --fail us-west@600")
    ap.add_argument("--trace", default=None, metavar="PATH",
                    help="with --wan-preset: record the WAN-projection "
                         "simulations of every combo this run executes and "
                         "export one Perfetto-loadable Chrome trace "
                         "(repro.obs; lanes are grouped per combo tag)")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default=OUT_DIR)
    args = ap.parse_args()

    tracer = None
    if args.trace:
        if not args.wan_preset:
            ap.error("--trace requires --wan-preset (it records the WAN-"
                     "projection simulation)")
        from repro import obs
        tracer = obs.RecordingTracer()

    os.makedirs(args.out, exist_ok=True)
    archs = [canon(args.arch)] if args.arch else ARCHS[:10]  # assigned 10
    shapes = [args.shape] if args.shape else list(shp.SHAPES)
    meshes = [args.mesh] if args.mesh else ["single", "multi"]

    for arch in archs:
        for shape in shapes:
            for mesh_name in meshes:
                tag = f"{arch}_{shape}_{mesh_name}_{args.boundary}"
                path = os.path.join(args.out, tag + ".json")
                if os.path.exists(path) and not args.force:
                    print(f"[cached] {tag}")
                    continue
                print(f"[dryrun] {tag} ...", flush=True)
                try:
                    res = run_one(arch, shape, mesh_name, args.boundary,
                                  fsdp=False if args.no_fsdp else None,
                                  relayout=args.relayout,
                                  wan_preset=args.wan_preset,
                                  wan_drift=args.wan_drift,
                                  wan_fleet=args.fleet,
                                  wan_fail=args.fail,
                                  tracer=tracer, trace_label=tag)
                except Exception as e:
                    res = {"arch": arch, "shape": shape, "mesh": mesh_name,
                           "boundary": args.boundary, "status": "error",
                           "error": f"{type(e).__name__}: {e}",
                           "traceback": traceback.format_exc()[-2000:]}
                with open(path, "w") as f:
                    json.dump(res, f, indent=1)
                status = res["status"]
                extra = ""
                if status == "ok":
                    r = res["roofline"]
                    extra = (f" compute={r['compute_s']:.3f}s mem={r['memory_s']:.3f}s "
                             f"coll={r['collective_s']:.4f}s dcn={r['dcn_bytes']/1e6:.1f}MB "
                             f"compile={res['compile_s']}s")
                print(f"[{status}] {tag}{extra}", flush=True)

    if tracer is not None:
        if tracer.n_events:
            from repro import obs
            from repro.core.validate import check_trace

            n_windows = check_trace(tracer)  # second witness before export
            obs.write_chrome_trace(tracer, args.trace, label="dryrun-wan")
            print(f"[trace] {tracer.n_events} events ({n_windows} windows "
                  f"crosschecked) -> {args.trace}")
        else:
            print("[trace] nothing recorded (all combos cached? use --force)")


if __name__ == "__main__":
    main()
