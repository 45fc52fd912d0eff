import os

os.environ["XLA_FLAGS"] = (
    os.environ.get("DRYRUN_XLA_FLAGS", "--xla_force_host_platform_device_count=512")
)

"""Multi-pod dry-run: lower + compile every (arch x shape x mesh) cell.

For each cell this proves, without hardware:
  * the sharding config is coherent (GSPMD partitions the whole step),
  * it fits v5e HBM (memory_analysis per-device bytes),
  * and it yields the roofline terms (cost_analysis FLOPs/bytes + collective
    bytes parsed from the partitioned HLO).

Usage:
  python -m repro.launch.dryrun --arch qwen2-1.5b --shape train_4k
  python -m repro.launch.dryrun --all [--multi-pod] [--out reports/dryrun]
"""

import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402

from repro.launch.hlo_costs import device_peaks, parse_hlo_costs  # noqa: E402

# The chip the cells are projected onto (``jax.Device.device_kind``); its
# peaks come from hlo_costs.DEVICE_PEAKS.
DEVICE_KIND = "TPU v5 lite"


def _finish_report(
    *, arch, shape, kind, mesh_name, n_dev, compiled, t_lower, t_compile,
    mf, out_dir,
):
    """Shared roofline/memory/collective reporting for any compiled cell."""
    mem = compiled.memory_analysis()
    ca = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    parsed = parse_hlo_costs(hlo)  # while bodies x trip count (hlo_costs.py)
    coll = parsed["collectives"]
    del hlo

    flops_dev = parsed["flops"]
    bytes_dev = parsed["bytes"]

    peaks = device_peaks(DEVICE_KIND)
    compute_term = flops_dev / peaks["flops_bf16"]
    memory_term = bytes_dev / peaks["hbm_bytes_per_s"]
    collective_term = coll["total"] / peaks["ici_link_bytes_per_s"]
    dominant = max(
        [("compute", compute_term), ("memory", memory_term),
         ("collective", collective_term)],
        key=lambda kv: kv[1],
    )[0]

    hbm_per_dev = (
        mem.argument_size_in_bytes
        + mem.output_size_in_bytes
        + mem.temp_size_in_bytes
        - mem.alias_size_in_bytes
    )
    report = {
        "arch": arch,
        "shape": shape,
        "kind": kind,
        "mesh": mesh_name,
        "n_devices": n_dev,
        "device_kind": DEVICE_KIND,
        "ok": True,
        "lower_s": round(t_lower, 1),
        "compile_s": round(t_compile, 1),
        "memory": {
            "argument_bytes": mem.argument_size_in_bytes,
            "output_bytes": mem.output_size_in_bytes,
            "temp_bytes": mem.temp_size_in_bytes,
            "alias_bytes": mem.alias_size_in_bytes,
            "hbm_per_device": hbm_per_dev,
            "fits_hbm": bool(hbm_per_dev < peaks["hbm_bytes"]),
        },
        "cost": {
            "flops_per_device": flops_dev,
            "bytes_per_device": bytes_dev,
            # raw HloCostAnalysis numbers (while bodies counted ONCE) for
            # reference — the parsed numbers above are the roofline inputs
            "xla_flops_unscaled": float(ca.get("flops", 0.0)),
            "xla_bytes_unscaled": float(ca.get("bytes accessed", 0.0)),
        },
        "collectives": coll,
        "model_flops": mf,
        "roofline": {
            "compute_s": compute_term,
            "memory_s": memory_term,
            "collective_s": collective_term,
            "dominant": dominant,
            "useful_flops_ratio": (
                mf["model_flops"] / (flops_dev * n_dev) if flops_dev else 0.0
            ),
        },
    }
    if out_dir:
        os.makedirs(out_dir, exist_ok=True)
        path = os.path.join(out_dir, f"{arch}__{shape}__{mesh_name}.json")
        with open(path, "w") as f:
            json.dump(report, f, indent=1)
    return report


def run_cell(arch: str, shape: str, multi_pod: bool, out_dir: str | None) -> dict:
    from repro.configs import SHAPES, get_config
    from repro.configs.flops import model_flops
    from repro.launch.mesh import make_production_mesh, policy_for
    from repro.launch.specs import cell_inputs
    from repro.train.optimizer import AdamWConfig
    from repro.train.step import make_decode_step, make_prefill_step, make_train_step

    cfg = get_config(arch)
    cell = SHAPES[shape]
    mesh = make_production_mesh(multi_pod=multi_pod)
    policy = policy_for(mesh)
    n_dev = mesh.size
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"

    t0 = time.time()
    inputs = cell_inputs(cfg, cell, policy)

    with mesh:
        if cell.kind == "train":
            fn = make_train_step(cfg, AdamWConfig(), policy)
            jfn = jax.jit(fn, donate_argnums=(0, 1))
            lowered = jfn.lower(inputs["params"], inputs["opt_state"], inputs["batch"])
        elif cell.kind == "prefill":
            fn = make_prefill_step(cfg, policy)
            jfn = jax.jit(fn)
            lowered = jfn.lower(inputs["params"], inputs["batch"])
        else:
            fn = make_decode_step(cfg, policy)
            jfn = jax.jit(fn, donate_argnums=(2,))
            lowered = jfn.lower(
                inputs["params"], inputs["tokens"], inputs["caches"], inputs["pos"]
            )
        t_lower = time.time() - t0
        compiled = lowered.compile()
        t_compile = time.time() - t0 - t_lower

    return _finish_report(
        arch=arch, shape=shape, kind=cell.kind, mesh_name=mesh_name,
        n_dev=n_dev, compiled=compiled, t_lower=t_lower, t_compile=t_compile,
        mf=model_flops(cfg, cell), out_dir=out_dir,
    )


# ------------------------------------------------------------- cluster cells

# The paper's own workload at production scale: n = 16.7M tf-idf documents
# (d=2048) sharded over the data axes, k=400 clusters (paper's 1GB setting,
# scaled to a TPU pod). One cell per MapReduce job kind.
CLUSTER_N = 1 << 24
CLUSTER_D = 2048
CLUSTER_K = 400
CLUSTER_BIGK = 800
CLUSTER_S = 81920  # Buckshot sample = sqrt(k n) rounded to shard multiple

CLUSTER_SHAPES = ("kmeans_iter", "bkc_microclusters", "boruvka_round",
                  "kmeans_iter_opt", "bkc_microclusters_opt")


def run_cluster_cell(shape: str, multi_pod: bool, out_dir: str | None) -> dict:
    import jax.numpy as jnp
    from jax.sharding import NamedSharding
    from jax.sharding import PartitionSpec as P

    from repro.distrib import cluster as dc
    from repro.distrib.engine import make_job
    from repro.kernels import ops
    from repro.launch.mesh import make_production_mesh, policy_for

    mesh = make_production_mesh(multi_pod=multi_pod)
    # clustering has no tensor-parallel dimension: ALL mesh axes carry rows
    # (the paper's 'nodes' == every chip in the pod)
    axes = tuple(mesh.axis_names)
    n_dev = mesh.size
    mesh_name = "multipod_2x16x16" if multi_pod else "pod_16x16"

    def sds(shape_, spec, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(
            shape_, dtype, sharding=NamedSharding(mesh, spec)
        )

    t0 = time.time()
    opt = shape.endswith("_opt")
    # §Perf H3: optimized variant — documents pre-zeroed (no x*w temp) and
    # stored bf16 on the wire/HBM with f32 accumulation (MXU-native).
    doc_dtype = jnp.bfloat16 if opt else jnp.float32
    if shape.startswith("kmeans_iter"):
        map_combine, kinds = dc._assign_stats_map(
            CLUSTER_K, "xla", prezeroed=opt, unit_norm=opt
        )
        job = make_job(mesh, axes, map_combine, kinds, name=shape)
        data = {
            "x": sds((CLUSTER_N, CLUSTER_D), P(axes, None), doc_dtype),
            "w": sds((CLUSTER_N,), P(axes)),
        }
        bcast = {"centers": sds((CLUSTER_K, CLUSTER_D), P(), doc_dtype)}
        lowered = job.lower(data, bcast)
        # useful work: similarity matmul + one-hot stats matmul + reductions
        mf = 4.0 * CLUSTER_N * CLUSTER_D * CLUSTER_K
    elif shape.startswith("bkc_microclusters"):
        # BKC job 1 at BigK micro-clusters (paper §3.3)
        map_combine, kinds = dc._assign_stats_map(
            CLUSTER_BIGK, "xla", prezeroed=opt, unit_norm=opt
        )
        job = make_job(mesh, axes, map_combine, kinds, name=shape)
        data = {
            "x": sds((CLUSTER_N, CLUSTER_D), P(axes, None), doc_dtype),
            "w": sds((CLUSTER_N,), P(axes)),
        }
        bcast = {"centers": sds((CLUSTER_BIGK, CLUSTER_D), P(), doc_dtype)}
        lowered = job.lower(data, bcast)
        mf = 4.0 * CLUSTER_N * CLUSTER_D * CLUSTER_BIGK
    elif shape == "boruvka_round":
        # one sharded Borůvka candidate round on the Buckshot sample —
        # matrix-free: the fused sim+best-edge op, no (s, s) block per shard
        def cand_map(data, bcast):
            return dict(
                zip(("j", "w"), ops.sim_best_edge(
                    data["rows"], bcast["xs"], data["labels"],
                    bcast["all_labels"], impl="xla",
                ))
            )

        job = make_job(
            mesh, axes, cand_map, {"j": "shard", "w": "shard"}, name="boruvka"
        )
        data = {
            "rows": sds((CLUSTER_S, CLUSTER_D), P(axes, None)),
            "labels": sds((CLUSTER_S,), P(axes), jnp.int32),
        }
        bcast = {
            "xs": sds((CLUSTER_S, CLUSTER_D), P()),
            "all_labels": sds((CLUSTER_S,), P(), jnp.int32),
        }
        lowered = job.lower(data, bcast)
        mf = 2.0 * CLUSTER_S * CLUSTER_S * CLUSTER_D
    else:
        raise KeyError(shape)

    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    return _finish_report(
        arch="cluster-tfidf", shape=shape, kind="cluster", mesh_name=mesh_name,
        n_dev=n_dev, compiled=compiled, t_lower=t_lower, t_compile=t_compile,
        mf={"model_flops": mf, "n": CLUSTER_N, "d": CLUSTER_D, "k": CLUSTER_K},
        out_dir=out_dir,
    )


def main() -> int:
    from repro.configs import cells_for, list_archs

    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--cluster", action="store_true",
                    help="run the clustering-engine cells (the paper's jobs)")
    ap.add_argument("--out", default="reports/dryrun")
    args = ap.parse_args()

    cells: list[tuple[str, str, bool]] = []
    if not args.cluster:
        archs = list_archs() if (args.all or not args.arch) else [args.arch]
        for arch in archs:
            shapes = (
                cells_for(arch) if (args.all or not args.shape) else [args.shape]
            )
            for shape in shapes:
                if args.both_meshes:
                    cells.append((arch, shape, False))
                    cells.append((arch, shape, True))
                else:
                    cells.append((arch, shape, args.multi_pod))
    if args.cluster or args.all:
        shapes = CLUSTER_SHAPES if not (args.cluster and args.shape) else [args.shape]
        for shape in shapes:
            if args.both_meshes:
                cells.append(("cluster-tfidf", shape, False))
                cells.append(("cluster-tfidf", shape, True))
            else:
                cells.append(("cluster-tfidf", shape, args.multi_pod))

    failures = 0
    for arch, shape, mp in cells:
        tag = f"{arch} x {shape} x {'2x16x16' if mp else '16x16'}"
        try:
            if arch == "cluster-tfidf":
                r = run_cluster_cell(shape, mp, args.out)
            else:
                r = run_cell(arch, shape, mp, args.out)
            rf = r["roofline"]
            print(
                f"OK   {tag:55s} compile={r['compile_s']:7.1f}s "
                f"hbm/dev={r['memory']['hbm_per_device']/2**30:6.2f}GiB "
                f"flops/dev={r['cost']['flops_per_device']:.3e} "
                f"coll={r['collectives']['total']:.3e}B "
                f"dom={rf['dominant']}"
            )
        except Exception as e:
            failures += 1
            print(f"FAIL {tag}: {type(e).__name__}: {str(e)[:300]}")
            traceback.print_exc()
    print(f"\n{len(cells) - failures}/{len(cells)} cells passed")
    return 1 if failures else 0


if __name__ == "__main__":
    raise SystemExit(main())
