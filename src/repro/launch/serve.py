"""Batched decode serving launcher (prefill + autoregressive decode loop).

    PYTHONPATH=src python -m repro.launch.serve --arch mixtral-8x7b --reduced \\
        --batch 4 --prompt-len 32 --gen 16

Thin front-end over :func:`repro.serve.driver.serve_once`; the elastic
serving path (resizes, cache migration) is exercised by
``benchmarks/bench_serve_goodput.py``.
"""

from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    args = ap.parse_args()

    from repro.configs import get_config
    from repro.serve.driver import serve_once
    from repro.utils.compile_cache import enable_compile_cache

    enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    out = serve_once(
        cfg,
        batch=args.batch,
        prompt_len=args.prompt_len,
        gen=args.gen,
        temperature=args.temperature,
    )
    toks = out["tokens"]
    print(f"[prefill] {args.batch}x{args.prompt_len} tokens in {out['prefill_s']:.2f}s")
    print(f"[decode] {args.gen} steps x batch {args.batch} in {out['decode_s']:.2f}s "
          f"({args.gen*args.batch/out['decode_s']:.1f} tok/s incl. first-step compile)")
    print("[sample] first request tokens:", [int(t) for t in toks[0][:12]])


if __name__ == "__main__":
    main()
