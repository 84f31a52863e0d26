"""Multi-pod dry-run: lower + compile every (architecture x shape x mesh)
cell with ShapeDtypeStruct stand-ins (no allocation), print memory/cost
analysis, and extract the roofline terms (EXPERIMENTS.md reads the JSON
this writes).

Run:  PYTHONPATH=src python -m repro.launch.dryrun --all --mesh single
      PYTHONPATH=src python -m repro.launch.dryrun --arch gemma3-1b --shape train_4k --mesh multi
Results accumulate in dryrun_results.json (resumable; --force to redo).
"""
# The 512 placeholder devices MUST be configured before jax initializes —
# these two lines precede every other import, including repro's.
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

import argparse
import json
import re
import sys
import time
import traceback

import jax
import jax.numpy as jnp

from repro import configs
from repro.core import lanczos as lz
from repro.core import similarity as sim
from repro.distrib import act_sharding, hlo_analysis, mesh_utils, sharding
from repro.launch.mesh import make_production_mesh, make_spectral_mesh
from repro.models import api
from repro.models import params as pp
from repro.models.config import SHAPES_BY_NAME
from repro.precision import matmul
from repro.train import optimizer as opt_lib
from repro.train.step import make_train_step
from repro.tune.peaks import DEVICE_PEAKS

# TPU v5e per-chip peaks from the one table
_V5E = DEVICE_PEAKS["tpu-v5-lite"]
PEAK_FLOPS = _V5E["flops"]   # bf16
HBM_BW = _V5E["bytes"]       # bytes/s
ICI_BW = _V5E["ici_link"]    # bytes/s/link

_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}

_ARRAY_RE = re.compile(r"(f64|f32|f16|bf16|f8e4m3|f8e5m2|s64|u64|s32|u32|s16|u16|s8|u8|pred)\[([0-9,]*)\]")
_COLL_OPS = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
             "collective-permute")


def _shape_bytes(segment: str) -> int:
    """Sum byte sizes of every array literal in an HLO type segment."""
    total = 0
    for dt, dims in _ARRAY_RE.findall(segment):
        numel = 1
        if dims:
            for d in dims.split(","):
                numel *= int(d)
        total += numel * _DTYPE_BYTES[dt]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-type result bytes, parsed from compiled HLO."""
    out = {k: 0 for k in _COLL_OPS}
    counts = {k: 0 for k in _COLL_OPS}
    for line in hlo_text.splitlines():
        ls = line.strip()
        eq = ls.find("= ")
        if eq < 0:
            continue
        rhs = ls[eq + 2:]
        for op in _COLL_OPS:
            # match the op as the instruction (e.g. "bf16[...] all-gather(")
            m = re.search(rf"\)*\s({op}|{op}-start|{op}-done)\(", rhs)
            if m:
                seg = rhs[: m.start()]
                if m.group(1).endswith("-done"):
                    continue  # counted at -start
                out[op] += _shape_bytes(seg)
                counts[op] += 1
                break
    return {"bytes": out, "counts": counts,
            "total_bytes": int(sum(out.values()))}


def _memory_dict(compiled) -> dict:
    try:
        ma = compiled.memory_analysis()
    except Exception:
        return {}
    keys = ("argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")
    d = {}
    for k in keys:
        v = getattr(ma, k, None)
        if v is not None:
            d[k] = int(v)
    if not d:
        d["repr"] = str(ma)
    return d


def _cost_dict(compiled) -> dict:
    try:
        ca = compiled.cost_analysis()
    except Exception:
        return {}
    if isinstance(ca, (list, tuple)):
        ca = ca[0]
    return {k: float(v) for k, v in ca.items()
            if isinstance(v, (int, float)) and (
                k in ("flops", "transcendentals") or k.startswith("bytes accessed"))}


def roofline_terms(hlo: dict) -> dict:
    """Three roofline terms in seconds, from the per-device (SPMD-
    partitioned) HLO costs with while-trip-count correction."""
    t_compute = hlo["flops"] / PEAK_FLOPS
    t_memory = hlo["bytes"] / HBM_BW
    t_collective = hlo["collective_total"] / ICI_BW
    dominant = max(
        (("compute", t_compute), ("memory", t_memory),
         ("collective", t_collective)), key=lambda kv: kv[1])[0]
    bound = max(t_compute, t_memory, t_collective)
    return {"t_compute_s": t_compute, "t_memory_s": t_memory,
            "t_collective_s": t_collective, "dominant": dominant,
            "roofline_fraction": t_compute / bound if bound > 0 else 0.0}


# ---------------------------------------------------------------------------
# cell lowering
# ---------------------------------------------------------------------------

def lower_lm_cell(arch: str, shape_name: str, multi_pod: bool,
                  cfg_override=None):
    cfg = cfg_override or configs.get(arch)
    cell = SHAPES_BY_NAME[shape_name]
    # SP pays off when compute is O(S) per step (prefill); decode streams
    # weights per token, so replicating them regresses — measured in
    # EXPERIMENTS.md §Perf (A4)
    if cell.kind == "prefill" and cfg.serve_sharding_preset \
            and not cfg.sharding_preset:
        cfg = cfg.with_(sharding_preset=cfg.serve_sharding_preset)
    model = api.build(cfg)
    mesh = make_production_mesh(multi_pod=multi_pod)
    p_shard = sharding.param_shardings(cfg, model.spec, mesh)
    abstract_p = model.abstract_params()
    batch = configs.input_specs(cfg, cell)
    b_shard = sharding.input_shardings(
        mesh, batch, seq_axis=sharding.seq_axis_for_inputs(cfg))

    if cell.kind == "train":
        optimizer = opt_lib.get(cfg.optimizer)
        o_spec = optimizer.init_spec(model.spec)
        o_shard = sharding.opt_shardings(cfg, o_spec, mesh)
        abstract_o = pp.abstract_params(o_spec)
        step = make_train_step(model, optimizer)
        jitted = jax.jit(step, in_shardings=(p_shard, o_shard, b_shard),
                         out_shardings=(p_shard, o_shard, None),
                         donate_argnums=(0, 1))
        with act_sharding.use_mesh(mesh):
            lowered = jitted.lower(abstract_p, abstract_o, batch)
    elif cell.kind == "prefill":
        c_spec = model.cache_specs(cell.global_batch, cell.seq_len)
        c_shard = sharding.cache_shardings(cfg, c_spec, mesh)

        def fn(p, b):
            return model.prefill(p, b, max_seq=cell.seq_len)

        jitted = jax.jit(fn, in_shardings=(p_shard, b_shard),
                         out_shardings=(None, c_shard))
        with act_sharding.use_mesh(mesh):
            lowered = jitted.lower(abstract_p, batch)
    elif cell.kind == "decode":
        c_spec = model.cache_specs(cell.global_batch, cell.seq_len)
        c_shard = sharding.cache_shardings(cfg, c_spec, mesh)
        abstract_c = pp.abstract_params(c_spec)
        jitted = jax.jit(model.decode_step,
                         in_shardings=(p_shard, c_shard, b_shard["token"]),
                         out_shardings=(None, c_shard),
                         donate_argnums=(1,))
        with act_sharding.use_mesh(mesh):
            lowered = jitted.lower(abstract_p, abstract_c, batch["token"])
    else:
        raise ValueError(cell.kind)
    return lowered, mesh, model


def _block_state_abs(n_pad: int, steps: int, b: int):
    """Shapes of a block-Lanczos state, nothing allocated."""
    return jax.eval_shape(
        lambda: lz.init_block_state(n_pad, steps, jax.random.PRNGKey(0), b))


def lower_spectral_cell(phase: str, multi_pod: bool, n: int | None = None):
    """Dry-run the paper pipeline's three phases on the flat mesh."""
    from repro.configs import spectral_paper
    mesh = make_spectral_mesh(multi_pod=multi_pod)
    m = mesh_utils.mesh_size(mesh)
    n = n or spectral_paper.PRODUCTION_N
    sched = sim.make_schedule(n, m)
    n_pad = sched.n_pad
    d_feat, k = 64, spectral_paper.CONFIG.k
    x_abs = jax.ShapeDtypeStruct((n, d_feat), jnp.float32)

    if phase == "similarity":
        def fn(x):
            up = sim.similarity_upper_blocks(x, 1.0, mesh, schedule=sched)
            return up.U
        lowered = jax.jit(fn).lower(x_abs)
    elif phase == "similarity_full":
        # beyond-paper variant: every device computes its whole row block
        # (2x pair-FLOPs, no triangle bookkeeping / mirror communication)
        def fn(x):
            return sim.distributed_similarity_full(x, 1.0, mesh)
        lowered = jax.jit(fn).lower(x_abs)
    elif phase == "similarity_compact":
        # perf iteration S1: triangular schedule with compact tile storage
        def fn(x):
            return sim.similarity_upper_blocks_compact(x, 1.0, mesh,
                                                       schedule=sched).tiles
        lowered = jax.jit(fn).lower(x_abs)
    elif phase == "lanczos_compact":
        from jax.sharding import NamedSharding, PartitionSpec as P
        m_dev = mesh_utils.mesh_size(mesh)
        tiles_abs = jax.ShapeDtypeStruct(
            (m_dev * (2 * m_dev + 1), sched.b, sched.b), jnp.float32)
        diag_abs = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
        st_abs = _block_state_abs(n_pad, 32, 1)
        t_shard = NamedSharding(mesh, P("rows", None, None))

        def fn(tiles, diag, state):
            up = sim.UpperSimCompact(tiles=tiles, diag=diag, schedule=sched,
                                     mesh=mesh, axis=("rows",))
            deg = sim.sym_matvec_compact(up, diag)
            inv = jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)

            def mm(V):
                return diag[:, None] * V + inv[:, None] * sim.sym_matmat_compact(
                    up, inv[:, None] * V)

            return lz.block_run(mm, state, 1)

        lowered = jax.jit(fn, in_shardings=(t_shard, None, None),
                          donate_argnums=(2,)).lower(tiles_abs, diag_abs, st_abs)
    elif phase == "lanczos_materialized":
        # paper-faithful alternative: Lanczos against the fully materialized
        # mirrored S (the Hadoop way: both triangles stored in HBase);
        # compare against the sym_matmat path that never mirrors
        from jax.sharding import NamedSharding, PartitionSpec as P
        S_abs = jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32)
        st_abs = _block_state_abs(n_pad, 32, 1)
        s_shard = NamedSharding(mesh, P("rows", None))

        def fn(S, state):
            valid = (jnp.arange(n_pad) < n).astype(jnp.float32)
            deg = matmul(S, valid)
            inv = jnp.where(deg > 0, 1.0 / jnp.sqrt(jnp.maximum(deg, 1e-12)), 0.0)

            def mm(V):
                return valid[:, None] * V + inv[:, None] * matmul(
                    S, inv[:, None] * V)

            return lz.block_run(mm, state, 1)

        lowered = jax.jit(fn, in_shardings=(s_shard, None),
                          donate_argnums=(1,)).lower(S_abs, st_abs)
    elif phase in ("lanczos", "block_lanczos"):
        # one Lanczos step against row-sharded upper blocks: the
        # (n_pad, b) block stays replicated, each device streams its row
        # block of U once per step — b vectors advanced per matrix pass
        # (b = 1 for the paper's single-vector recurrence)
        from jax.sharding import NamedSharding, PartitionSpec as P
        blk, steps = (1, 32) if phase == "lanczos" else (8, 8)
        U_abs = jax.ShapeDtypeStruct((n_pad, n_pad), jnp.float32)
        diag_abs = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
        st_abs = _block_state_abs(n_pad, steps, blk)
        u_shard = NamedSharding(mesh, P("rows", None))

        def fn(U, diag, state):
            up = sim.UpperSim(U=U, diag=diag, schedule=sched, mesh=mesh,
                              axis=("rows",))
            from repro.core import laplacian as lp
            deg = lp.degrees(up)
            mm = lp.make_shifted_matmat(up, deg)
            return lz.block_run(mm, state, 1)

        lowered = jax.jit(fn, in_shardings=(u_shard, None, None),
                          donate_argnums=(2,)).lower(U_abs, diag_abs, st_abs)
    elif phase == "kmeans":
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.core import kmeans as km
        y_abs = jax.ShapeDtypeStruct((n_pad, k), jnp.float32)
        v_abs = jax.ShapeDtypeStruct((n_pad,), jnp.float32)
        st = km.KMeansState(it=jnp.zeros((), jnp.int32),
                            centers=jnp.zeros((k, k)), shift=jnp.zeros(()))
        st_abs = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), st)

        def fn(y, valid, state):
            return km.distributed_lloyd_step(y, valid, state, mesh)

        lowered = jax.jit(
            fn, in_shardings=(NamedSharding(mesh, P("rows", None)), None, None)
        ).lower(y_abs, v_abs, st_abs)
    else:
        raise ValueError(phase)
    return lowered, mesh, None


def _parse_overrides(pairs: list[str]):
    """--override key=value: ints, floats, bools, and bare strings."""
    out = {}
    for p in pairs or []:
        k, v = p.split("=", 1)
        if v in ("True", "true"):
            out[k] = True
        elif v in ("False", "false"):
            out[k] = False
        else:
            try:
                out[k] = int(v)
            except ValueError:
                try:
                    out[k] = float(v)
                except ValueError:
                    out[k] = v
    return out


def run_cell(arch: str, shape_name: str, multi_pod: bool, verbose: bool = True,
             overrides: dict | None = None, tag: str = ""):
    t0 = time.perf_counter()
    if arch == "spectral":
        lowered, mesh, model = lower_spectral_cell(shape_name, multi_pod)
    else:
        cfg = configs.get(arch)
        if overrides:
            cfg = cfg.with_(**overrides)
        lowered, mesh, model = lower_lm_cell(arch, shape_name, multi_pod,
                                             cfg_override=cfg)
    t_lower = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    t_compile = time.perf_counter() - t0

    n_chips = mesh_utils.mesh_size(mesh)
    mem = _memory_dict(compiled)
    cost = _cost_dict(compiled)        # raw XLA numbers (loop bodies once)
    t0 = time.perf_counter()
    hlo = hlo_analysis.analyze(compiled.as_text())
    t_analyze = time.perf_counter() - t0
    roof = roofline_terms(hlo)
    rec = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi" if multi_pod else "single",
        "tag": tag, "overrides": overrides or {},
        "n_chips": n_chips,
        "lower_s": round(t_lower, 1), "compile_s": round(t_compile, 1),
        "analyze_s": round(t_analyze, 1),
        "memory": mem, "cost_analysis_raw": cost, "hlo": hlo,
        "roofline": roof,
    }
    if model is not None:
        rec["num_params"] = model.num_params()
        rec["num_active_params"] = model.num_active_params()
    if verbose:
        print(f"[dryrun] {arch} x {shape_name} x {rec['mesh']}  "
              f"compile={t_compile:.0f}s", flush=True)
        print(f"  memory_analysis: {mem}", flush=True)
        print(f"  hlo(per-dev): flops={hlo['flops']:.3e} bytes={hlo['bytes']:.3e} "
              f"coll={hlo['collective_bytes']}", flush=True)
        print(f"  roofline: {roof}", flush=True)
    return rec


# ---------------------------------------------------------------------------
# CLI with resumable JSON accumulation
# ---------------------------------------------------------------------------

def all_cells():
    for arch in configs.ARCHS:
        for shape in SHAPES_BY_NAME:
            yield arch, shape
    for phase in ("similarity", "lanczos", "block_lanczos", "kmeans"):
        yield "spectral", phase


def cell_key(arch, shape, mesh_name):
    return f"{arch}|{shape}|{mesh_name}"


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--mesh", default="single", choices=["single", "multi", "both"])
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--out", default="dryrun_results.json")
    ap.add_argument("--override", action="append", default=[],
                    help="cfg field override key=value (perf variants)")
    ap.add_argument("--tag", default="",
                    help="variant tag appended to the result key")
    args = ap.parse_args(argv)
    overrides = _parse_overrides(args.override)

    results = {}
    if os.path.exists(args.out):
        with open(args.out) as f:
            results = json.load(f)

    if args.all:
        cells = list(all_cells())
    else:
        assert args.arch and args.shape, "--arch/--shape or --all"
        cells = [(args.arch, args.shape)]
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    failures = []
    for arch, shape in cells:
        if arch != "spectral" and not configs.cell_supported(arch, shape):
            for mp in meshes:
                key = cell_key(arch, shape, "multi" if mp else "single")
                results[key] = {"arch": arch, "shape": shape,
                                "mesh": "multi" if mp else "single",
                                "skipped": "unsupported (see DESIGN.md §5)"}
            continue
        for mp in meshes:
            key = cell_key(arch, shape, "multi" if mp else "single")
            if args.tag:
                key += f"|{args.tag}"
            if key in results and not args.force and "error" not in results[key]:
                continue
            try:
                results[key] = run_cell(arch, shape, mp, overrides=overrides,
                                        tag=args.tag)
            except Exception as e:
                traceback.print_exc()
                results[key] = {"arch": arch, "shape": shape,
                                "mesh": "multi" if mp else "single",
                                "error": f"{type(e).__name__}: {e}"}
                failures.append(key)
            with open(args.out, "w") as f:
                json.dump(results, f, indent=1)
    done = sum(1 for r in results.values() if "error" not in r and "skipped" not in r)
    skip = sum(1 for r in results.values() if "skipped" in r)
    print(f"[dryrun] complete: {done} ok, {skip} skipped, {len(failures)} failed")
    if failures:
        print("failed:", failures)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
