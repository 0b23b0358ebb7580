import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
# ^ MUST precede every other import (jax locks device count on first init).
# (No `from __future__` here for that reason — py3.13 needs no annotations
# import anyway.)
# NOTE on XLA cost_analysis: while-loop bodies are counted ONCE (not x
# trip count).  The deliverable compile therefore uses the rolled scan
# (production HLO, honest memory analysis), and roofline FLOPs/bytes/
# collective-traffic are obtained from two small-L *unrolled* lowerings,
# extrapolated linearly over the (homogeneous) layer stack:
#     F_L = F(1) + (L - 1) * (F(2) - F(1))
# which is exact for scanned stacks and validated against a full-unroll
# build in EXPERIMENTS.md (qwen3 train_4k: <1% error).

"""Multi-pod dry-run: prove the distribution config is coherent.

For every (arch x shape-cell), lower + compile the train/prefill/serve
step from ShapeDtypeStructs on the production mesh — 16x16 single-pod and
2x16x16 multi-pod — and record memory_analysis / cost_analysis plus the
collective-traffic breakdown parsed from the compiled HLO.  Results land
in benchmarks/dryrun_results/*.json, one file per (arch, cell, mesh).

Usage:
    python -m repro.launch.dryrun --arch llama3-8b --cell train_4k
    python -m repro.launch.dryrun --arch llama3-8b --cell train_4k --multi-pod
    python -m repro.launch.dryrun --all            # every cell, both meshes
"""
import argparse
import json
import pathlib
import re
import subprocess
import sys
import time

RESULTS_DIR = pathlib.Path(__file__).resolve().parents[3] / \
    "benchmarks" / "dryrun_results"

# `%name = <shape> <op>(...)`: capture the shape expression then the op.
_COLL_RE = re.compile(
    r"=\s*(\(?[a-z0-9\[\],{}: /#()]*?\)?)\s*"
    r"(all-reduce|all-gather|reduce-scatter|all-to-all|collective-permute)"
    r"(-start)?\(")
_SHAPE_RE = re.compile(r"\b([a-z0-9]+)\[([0-9,]*)\]")
_DTYPE_BYTES = {
    "f64": 8, "f32": 4, "f16": 2, "bf16": 2, "f8e4m3fn": 1, "f8e5m2": 1,
    "s64": 8, "u64": 8, "s32": 4, "u32": 4, "s16": 2, "u16": 2,
    "s8": 1, "u8": 1, "pred": 1,
}


def _shape_bytes(dtype: str, dims: str) -> int:
    n = 1
    if dims:
        for d in dims.split(","):
            n *= int(d)
    return n * _DTYPE_BYTES.get(dtype, 4)


def collective_stats(hlo: str) -> dict:
    """Sum result bytes of every collective op in the compiled HLO, by kind.

    The result shape of an op sits between `=` and the op name:
    ``%x = bf16[16,2048]{1,0} all-reduce(%y), ...``.  ``-start/-done``
    pairs are counted once (on the -start).  NOTE: ops inside while-loop
    bodies appear once; the dry-run unrolls the layer scan so per-layer
    collectives are correctly multiplied."""
    out: dict[str, dict] = {}
    for line in hlo.splitlines():
        m = _COLL_RE.search(line)
        if not m:
            continue
        kind = m.group(2)
        b = sum(_shape_bytes(d, s) for d, s in _SHAPE_RE.findall(m.group(1)))
        if b == 0:
            continue
        rec = out.setdefault(kind, {"count": 0, "bytes": 0})
        rec["count"] += 1
        rec["bytes"] += b
    return out


def n_params(tree) -> int:
    import jax
    import numpy as np
    return int(sum(np.prod(l.shape)
                   for l in jax.tree_util.tree_leaves(tree)))


def active_params(cfg, params) -> tuple[int, int]:
    """(N_matmul_total, N_matmul_active): matrix params (ndim>=2, no embed),
    with routed-expert stacks scaled by top_k/E for the active count."""
    import jax
    import numpy as np
    total = active = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        names = [getattr(p, "key", None) for p in path]
        if leaf.ndim < 2 or names[-1] == "embed":
            continue
        n = int(np.prod(leaf.shape))
        total += n
        if cfg.n_experts and "moe" in names and names[-1] in ("wg", "wu", "wd"):
            active += n * cfg.top_k // cfg.n_experts
        else:
            active += n
    return total, active


def _lower_cell(cfg, cell, mesh):
    """Lower the cell's step on the mesh; returns (lowered, model_tokens,
    flops_per_param)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from ..models import build_model, input_specs
    from ..parallel.sharding import batch_pspecs, data_axes, param_shardings
    from ..train.state import abstract_state, state_shardings
    from ..train.step import (make_decode_step, make_prefill_step,
                              make_train_step)

    import contextlib

    def mesh_ctx():
        # ambient mesh so P-only with_sharding_constraint resolves
        return jax.sharding.set_mesh(mesh)
    specs = input_specs(cfg, cell)
    bspecs = batch_pspecs(mesh, specs)
    to_sh = lambda tree: jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), tree,
        is_leaf=lambda x: isinstance(x, P))

    if cell.kind == "train":
        st = abstract_state(cfg)
        st_sh = state_shardings(mesh, cfg, st)
        step = make_train_step(cfg)
        with mesh_ctx():
            lowered = jax.jit(
                step, in_shardings=(st_sh, to_sh(bspecs)),
                out_shardings=(st_sh, None),
            ).lower(st, specs)
        return lowered, cell.global_batch * cell.seq_len, 6
    model = build_model(cfg)
    pspecs = jax.eval_shape(lambda: model.init(0))
    p_sh = param_shardings(mesh, pspecs)
    if cell.kind == "prefill":
        step = make_prefill_step(cfg)
        with mesh_ctx():
            lowered = jax.jit(
                step, in_shardings=(p_sh, to_sh(bspecs)),
            ).lower(pspecs, specs)
        return lowered, cell.global_batch * cell.seq_len, 2
    step = make_decode_step(cfg)
    tok_spec = P(data_axes(mesh)) if cell.global_batch > 1 else P(None)
    tok_sh = NamedSharding(mesh, tok_spec)
    with mesh_ctx():
        lowered = jax.jit(
            step,
            in_shardings=(p_sh, to_sh(bspecs["cache"]), tok_sh),
            out_shardings=(tok_sh, to_sh(bspecs["cache"])),
        ).lower(pspecs, specs["cache"], specs["tokens"])
    return lowered, cell.global_batch, 2


def _cost_of(compiled) -> dict:
    cost = compiled.cost_analysis() or {}
    hlo = compiled.as_text()
    colls = collective_stats(hlo)
    return {
        "flops": float(cost.get("flops", 0.0)),
        "bytes": float(cost.get("bytes accessed", 0.0)),
        "collectives": colls,
        "coll_bytes": float(sum(c["bytes"] for c in colls.values())),
    }


def _quad_extrap(ls, ys, L):
    """Quadratic (Lagrange) fit through 3 (l, y) points, evaluated at L.

    Per-layer HLO cost creeps superlinearly with depth (XLA's
    rematerialization grows under memory pressure); a quadratic fit
    matches full-unroll ground truth to ~0.1% (EXPERIMENTS.md)."""
    (x0, x1, x2), (y0, y1, y2) = ls, ys
    t0 = y0 * (L - x1) * (L - x2) / ((x0 - x1) * (x0 - x2))
    t1 = y1 * (L - x0) * (L - x2) / ((x1 - x0) * (x1 - x2))
    t2 = y2 * (L - x0) * (L - x1) / ((x2 - x0) * (x2 - x1))
    return max(0.0, t0 + t1 + t2)


def _roofline_probe(cfg, cell, mesh, unroll_layers: tuple[int, int, int]):
    """Three small-L UNROLLED lowerings -> quadratic extrapolation."""
    import dataclasses as dc
    os.environ["REPRO_SCAN_UNROLL"] = "1"
    try:
        ls = list(unroll_layers)
        probes = {}
        for l in ls:
            sub = {"n_layers": l}
            if cfg.enc_layers:
                sub["enc_layers"] = l
            c1 = dc.replace(cfg, **sub)
            lowered, _, _ = _lower_cell(c1, cell, mesh)
            probes[l] = _cost_of(lowered.compile())
        L = cfg.n_layers
        out = {}
        for fld in ("flops", "bytes", "coll_bytes"):
            out[fld] = _quad_extrap(ls, [probes[l][fld] for l in ls], L)
        kinds = set().union(*(probes[l]["collectives"].keys() for l in ls))
        colls = {}
        for k in kinds:
            bs = [probes[l]["collectives"].get(k, {}).get("bytes", 0)
                  for l in ls]
            ns = [probes[l]["collectives"].get(k, {}).get("count", 0)
                  for l in ls]
            colls[k] = {"bytes": _quad_extrap(ls, bs, L),
                        "count": _quad_extrap(ls, ns, L)}
        out["collectives"] = colls
        out["probe_layers"] = ls
        return out
    finally:
        os.environ["REPRO_SCAN_UNROLL"] = "0"


def run_cell(arch: str, cell_name: str, multi_pod: bool,
             out_path: pathlib.Path | None = None,
             with_roofline: bool = True, full_unroll: bool = False) -> dict:
    import jax

    from ..configs import get_config
    from ..configs.base import SHAPES
    from ..models import build_model
    from ..train.state import abstract_state
    from .mesh import make_production_mesh

    cfg = get_config(arch)
    cell = SHAPES[cell_name]
    mesh = make_production_mesh(multi_pod=multi_pod)
    mesh_name = "2x16x16" if multi_pod else "16x16"

    # ---- deliverable: production (rolled-scan) compile -------------------
    os.environ["REPRO_SCAN_UNROLL"] = "1" if full_unroll else "0"
    t0 = time.time()
    lowered, model_tokens, flops_per_param = _lower_cell(cfg, cell, mesh)
    t_lower = time.time() - t0
    compiled = lowered.compile()
    t_compile = time.time() - t0 - t_lower
    mem = compiled.memory_analysis()
    rolled_cost = _cost_of(compiled)

    def _mem_attr(name):
        try:
            return int(getattr(mem, name))
        except Exception:
            return None

    if cell.kind == "train":
        ptree = abstract_state(cfg).params
    else:
        ptree = jax.eval_shape(lambda: build_model(cfg).init(0))
    n_total, n_active = active_params(cfg, ptree)
    model_flops = flops_per_param * n_active * model_tokens

    result = {
        "arch": arch, "cell": cell_name, "mesh": mesh_name,
        "n_devices": 512 if multi_pod else 256,
        "kind": cell.kind,
        "seq_len": cell.seq_len, "global_batch": cell.global_batch,
        "compile_ok": True,
        "full_unroll": full_unroll,
        "rolled": rolled_cost,
        "mem_argument_bytes": _mem_attr("argument_size_in_bytes"),
        "mem_output_bytes": _mem_attr("output_size_in_bytes"),
        "mem_temp_bytes": _mem_attr("temp_size_in_bytes"),
        "n_params_matmul": n_total,
        "n_params_active": n_active,
        "model_flops_global": float(model_flops),
        "model_tokens": model_tokens,
        "lower_s": round(t_lower, 2), "compile_s": round(t_compile, 2),
        "hlo_bytes": len(compiled.as_text()),
    }

    # ---- roofline probe: small-L unrolled extrapolation (single-pod) -----
    if with_roofline and not multi_pod and not full_unroll:
        if cfg.family == "hybrid":
            p = cfg.shared_attn_period
            probe = _roofline_probe(cfg, cell, mesh, (p, 2 * p, 3 * p))
        else:
            probe = _roofline_probe(cfg, cell, mesh, (1, 2, 4))
        result["roofline"] = probe
    elif full_unroll:
        result["roofline"] = dict(rolled_cost,
                                  coll_bytes=rolled_cost["coll_bytes"],
                                  probe_layers="full")

    print(json.dumps(result, indent=1))
    print("memory_analysis:", mem)
    if out_path:
        out_path.parent.mkdir(parents=True, exist_ok=True)
        out_path.write_text(json.dumps(result, indent=1))
    return result


def _cell_path(arch, cell, multi_pod):
    mesh = "2x16x16" if multi_pod else "16x16"
    return RESULTS_DIR / f"{arch}__{cell}__{mesh}.json"


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--cell")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--full-unroll", action="store_true",
                    help="ground-truth unrolled build (slow; hillclimb cells)")
    ap.add_argument("--no-roofline", action="store_true")
    args = ap.parse_args()

    if args.all:
        from ..configs import all_cells
        todo = [(a, c, mp) for a, c in all_cells() for mp in (False, True)]
        failed = []
        for arch, cell, mp in todo:
            path = _cell_path(arch, cell, mp)
            if path.exists() and not args.force:
                print(f"skip (cached): {path.name}")
                continue
            cmd = [sys.executable, "-m", "repro.launch.dryrun",
                   "--arch", arch, "--cell", cell]
            if mp:
                cmd.append("--multi-pod")
            print(f"=== {arch} {cell} {'2x16x16' if mp else '16x16'} ===",
                  flush=True)
            r = subprocess.run(cmd, cwd=str(RESULTS_DIR.parents[1]))
            if r.returncode != 0:
                failed.append((arch, cell, mp))
        if failed:
            print("FAILED cells:", failed)
            sys.exit(1)
        print("ALL CELLS PASSED")
        return

    out = _cell_path(args.arch, args.cell, args.multi_pod)
    run_cell(args.arch, args.cell, args.multi_pod, out,
             with_roofline=not args.no_roofline,
             full_unroll=args.full_unroll)


if __name__ == "__main__":
    main()
