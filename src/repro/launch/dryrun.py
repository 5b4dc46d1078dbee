import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"
os.environ["JAX_PLATFORMS"] = "cpu"  # host-device emulation; never claims a chip

"""Multi-pod dry-run driver.

Lowers + compiles every (architecture × input-shape) cell against the
production mesh — 16×16 single-pod and 2×16×16 multi-pod — and records
memory analysis, cost analysis and collective bytes for the roofline table.

MUST be run as its own process (the XLA_FLAGS / JAX_PLATFORMS lines above
precede every other import because jax locks the devices at first init;
``--sweep`` children inherit both, so none of them reaches for a chip):

    PYTHONPATH=src python -m repro.launch.dryrun --arch mixtral-8x7b --shape train_4k
    PYTHONPATH=src python -m repro.launch.dryrun --sweep --out results/dryrun
    PYTHONPATH=src python -m repro.launch.dryrun --sweep --multi-pod

``--sweep`` spawns one subprocess per cell (isolation: a single cell's
failure or memory growth cannot poison the rest) and caches results as JSON.
"""

import argparse
import json
import subprocess
import sys
import time
import traceback


def _lower_cell(cfg, shape, mesh, opts: dict):
    """Build + lower + compile one cell; returns (compiled, aux_info)."""
    import jax

    from repro.models import model as M
    from repro.optim import AdamWConfig, adamw_init

    aparams = M.abstract_params(cfg)
    if shape.kind == "train":
        from repro.distribution.step import jit_train_step

        chips = int(mesh.devices.size)
        dp_total = chips // 16
        microbatches = opts.get(
            "microbatches", max(1, shape.global_batch // (dp_total * 2))
        )
        jitted, _ = jit_train_step(
            cfg,
            mesh,
            AdamWConfig(),
            shape.global_batch,
            microbatches=microbatches,
            remat=opts.get("remat", "full"),
            hint_version=opts.get("hints"),
            grad_accum=opts.get("grad_accum", "explicit"),
        )
        aopt = jax.eval_shape(lambda: adamw_init(aparams))
        abatch = M.input_specs(cfg, shape)
        args = (aparams, aopt, abatch)
        used = {"microbatches": microbatches}
    elif shape.kind == "prefill":
        from repro.distribution.step import jit_prefill_step

        jitted, _ = jit_prefill_step(
            cfg, mesh, shape.global_batch, shape.seq_len,
            hint_version=opts.get("hints"),
        )
        args = (aparams, M.input_specs(cfg, shape))
        used = {}
    else:
        from repro.distribution.step import jit_decode_step

        jitted, _ = jit_decode_step(
            cfg, mesh, shape.global_batch, shape.seq_len,
            serve_params=opts.get("serve_params", "fsdp"),
        )
        specs = M.input_specs(cfg, shape)
        args = [aparams, specs["cache"], specs["tokens"], specs["pos"]]
        if cfg.family == "encdec":
            args.append(specs["cross_kv"])
        args = tuple(args)
        used = {}
    return jitted, args, used


def _probe_costs(cfg, shape, mesh, opts: dict) -> dict:
    """3-probe linear cost model: XLA cost analysis counts a while body once,
    so we compile tiny UNROLLED variants (N periods ∈ {1,2}, microbatches M ∈
    {1,2}) and recover  X(N,M) = M·(N·body + per_mb) + step_out  exactly for
    flops / bytes / per-kind collective bytes."""
    import dataclasses

    from repro.models.transformer import block_program, n_periods
    from repro.roofline.analysis import collective_bytes_from_hlo

    period = len(block_program(cfg))
    n_full = n_periods(cfg)

    def probe(k_periods: int, m: int) -> dict:
        pcfg = dataclasses.replace(
            cfg,
            num_layers=period * k_periods,
            encoder_layers=k_periods if cfg.encoder_layers else 0,
        )
        shape_opts = dict(opts)
        shape_opts["microbatches"] = m
        os.environ["REPRO_SCAN_UNROLL"] = "1"
        try:
            jitted, args, _ = _lower_cell(pcfg, shape, mesh, shape_opts)
            compiled = jitted.lower(*args).compile()
        finally:
            os.environ.pop("REPRO_SCAN_UNROLL", None)
        cost = compiled.cost_analysis() or {}
        per = collective_bytes_from_hlo(compiled.as_text())
        return {
            "flops": float(cost.get("flops", 0.0)),
            "bytes": float(cost.get("bytes accessed", 0.0)),
            **{f"coll_{k}": float(v) for k, v in per.items()},
        }

    is_train = shape.kind == "train"
    x11 = probe(1, 1)
    x21 = probe(2, 1)
    # M-independence check: per-microbatch work is linear in tokens, so
    # flops/bytes are invariant to the accumulation factor (the x12 probe
    # validates this per cell; the tiny per-microbatch accumulate adds and
    # per-step optimizer work live in x11 already).
    x12 = probe(1, 2) if is_train else None

    chips = int(mesh.devices.size)
    dp_total = chips // 16
    m_full = (
        opts.get("microbatches", max(1, shape.global_batch // (dp_total * 2)))
        if is_train
        else 1
    )

    # X(N) = x11 + (N-1) * body ;  body = x21 - x11
    out = {}
    for key in x11:
        body = x21[key] - x11[key]
        out[key] = max(x11[key] + (n_full - 1) * body, 0.0)
    out["probe_model"] = {
        "n_periods": n_full, "microbatches": m_full,
        "x11": x11, "x21": x21, "x12": x12,
    }
    return out


def _run_cell(arch: str, shape_name: str, multi_pod: bool, out_dir: str,
              save_hlo: bool = False, opts: dict | None = None) -> dict:
    import jax
    import jax.numpy as jnp

    from repro.configs import SHAPES, get_config, shape_applicable
    from repro.launch.mesh import make_production_mesh
    from repro.models import model as M
    from repro.optim import AdamWConfig, adamw_init
    from repro.roofline.analysis import roofline_terms

    opts = opts or {}
    cfg = get_config(arch)
    if opts.get("param_dtype"):
        import dataclasses

        cfg = dataclasses.replace(cfg, param_dtype=opts["param_dtype"])
    shape = SHAPES[shape_name]
    mesh_desc = "pod2x16x16" if multi_pod else "pod16x16"
    ok, reason = shape_applicable(cfg, shape_name)
    if not ok:
        return {
            "arch": arch, "shape": shape_name, "mesh": mesh_desc,
            "status": "skipped", "reason": reason,
        }

    mesh = make_production_mesh(multi_pod=multi_pod)
    chips = int(mesh.devices.size)
    t_all = time.perf_counter()

    n_active = M.analytic_param_count(cfg, active_only=True)
    n_total = M.analytic_param_count(cfg)
    if shape.kind == "train":
        model_flops = 6.0 * n_active * shape.global_batch * shape.seq_len
    elif shape.kind == "prefill":
        model_flops = 2.0 * n_active * shape.global_batch * shape.seq_len
    else:
        model_flops = 2.0 * n_active * shape.global_batch

    # 1) full compile: proves the cell lowers/fits; memory analysis
    jitted, args, used_opts = _lower_cell(cfg, shape, mesh, opts)
    t0 = time.perf_counter()
    lowered = jitted.lower(*args)
    lower_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    mem = compiled.memory_analysis()
    mem_dict = {}
    if mem is not None:
        for k in (
            "argument_size_in_bytes",
            "output_size_in_bytes",
            "temp_size_in_bytes",
            "generated_code_size_in_bytes",
            "alias_size_in_bytes",
        ):
            v = getattr(mem, k, None)
            if v is not None:
                mem_dict[k] = int(v)
    hlo = compiled.as_text()

    # 2) cost probes (trip-count-exact flops/bytes/collectives).
    # cost_analysis numbers are for the per-partition (per-chip) module;
    # scale by chip count so the roofline formulas (which divide by chips)
    # see global totals.
    probed = _probe_costs(cfg, shape, mesh, opts)
    cost_for_report = {
        "flops": probed["flops"] * chips,
        "bytes accessed": probed["bytes"] * chips,
    }
    report = roofline_terms(
        arch, shape_name, mesh_desc, chips, cost_for_report, "", model_flops
    )
    report.per_collective = {
        k[len("coll_"):]: v * chips for k, v in probed.items() if k.startswith("coll_")
    }
    report.collective_bytes = int(sum(report.per_collective.values()))

    result = {
        "arch": arch,
        "shape": shape_name,
        "mesh": mesh_desc,
        "status": "ok",
        "chips": chips,
        "params_total": n_total,
        "params_active": n_active,
        "lower_s": lower_s,
        "compile_s": compile_s,
        "total_s": time.perf_counter() - t_all,
        "memory_analysis": mem_dict,
        "probe_model": probed["probe_model"],
        "opts": {**opts, **used_opts},
        **report.to_dict(),
    }
    if save_hlo and out_dir:
        os.makedirs(out_dir, exist_ok=True)
        tag = f"{arch}_{shape_name}_{mesh_desc}"
        with open(os.path.join(out_dir, f"hlo_{tag}.txt"), "w") as f:
            f.write(hlo)
    return result


def _result_path(out_dir: str, arch: str, shape: str, mesh_desc: str, tag: str = "") -> str:
    suffix = f"_{tag}" if tag else ""
    return os.path.join(out_dir, f"{arch}_{shape}_{mesh_desc}{suffix}.json")


def sweep(out_dir: str, multi_pod: bool, archs=None, shapes=None, force=False) -> None:
    from repro.configs import ASSIGNED, SHAPES

    os.makedirs(out_dir, exist_ok=True)
    archs = archs or list(ASSIGNED)
    shapes = shapes or list(SHAPES)
    mesh_desc = "pod2x16x16" if multi_pod else "pod16x16"
    todo = []
    for a in archs:
        for s in shapes:
            p = _result_path(out_dir, a, s, mesh_desc)
            if force or not os.path.exists(p):
                todo.append((a, s, p))
    print(f"[sweep] {len(todo)} cells to run ({mesh_desc})", flush=True)
    for i, (a, s, p) in enumerate(todo):
        cmd = [
            sys.executable, "-m", "repro.launch.dryrun",
            "--arch", a, "--shape", s, "--out", out_dir,
        ]
        if multi_pod:
            cmd.append("--multi-pod")
        print(f"[sweep {i+1}/{len(todo)}] {a} x {s} ({mesh_desc})", flush=True)
        t0 = time.time()
        r = subprocess.run(cmd, capture_output=True, text=True)
        dt = time.time() - t0
        if r.returncode != 0:
            err = {
                "arch": a, "shape": s, "mesh": mesh_desc, "status": "error",
                "stderr": r.stderr[-4000:], "seconds": dt,
            }
            with open(p, "w") as f:
                json.dump(err, f, indent=2)
            print(f"  ERROR after {dt:.0f}s: {r.stderr.splitlines()[-1] if r.stderr else '?'}", flush=True)
        else:
            print(f"  done in {dt:.0f}s", flush=True)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch")
    ap.add_argument("--shape")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--sweep", action="store_true")
    ap.add_argument("--force", action="store_true")
    ap.add_argument("--save-hlo", action="store_true")
    ap.add_argument("--out", default="results/dryrun")
    ap.add_argument("--tag", default="")
    ap.add_argument("--opts", default="{}", help="JSON dict: microbatches/remat/...")
    args = ap.parse_args()

    if args.sweep:
        archs = [args.arch] if args.arch else None
        shapes = [args.shape] if args.shape else None
        sweep(args.out, args.multi_pod, archs=archs, shapes=shapes, force=args.force)
        return

    assert args.arch and args.shape, "--arch and --shape required (or --sweep)"
    mesh_desc = "pod2x16x16" if args.multi_pod else "pod16x16"
    try:
        res = _run_cell(
            args.arch, args.shape, args.multi_pod, args.out,
            save_hlo=args.save_hlo, opts=json.loads(args.opts),
        )
    except Exception:
        res = {
            "arch": args.arch, "shape": args.shape, "mesh": mesh_desc,
            "status": "error", "stderr": traceback.format_exc()[-4000:],
        }
    os.makedirs(args.out, exist_ok=True)
    path = _result_path(args.out, args.arch, args.shape, mesh_desc, args.tag)
    with open(path, "w") as f:
        json.dump(res, f, indent=2)
    print(json.dumps({k: v for k, v in res.items() if k not in ("per_collective",)}, indent=2))
    if res["status"] == "error":
        print(res.get("stderr", ""), file=sys.stderr)
        sys.exit(1)


if __name__ == "__main__":
    main()
