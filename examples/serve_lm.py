"""Batched LM serving: prefill + greedy decode with KV caches.

    PYTHONPATH=src python examples/serve_lm.py [--arch qwen3-1.7b]

Requests with ragged prompt lengths are batched, prefilled in one
shot, and decoded with per-request kv_len masking — the serve path the
decode_32k / long_500k dry-run cells lower onto the pod (split-K KV
sharding, launch/mesh.py cache_specs).
"""
import argparse

from repro import compile_cache
from repro.launch.serve import serve_batch


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--requests", type=int, default=6)
    ap.add_argument("--gen", type=int, default=24)
    args = ap.parse_args()
    compile_cache.enable()
    out = serve_batch(args.arch, num_requests=args.requests,
                      prompt_len=48, gen_len=args.gen)
    print(f"generated {out['generated'].shape[0]} x "
          f"{out['generated'].shape[1]} tokens")
    print(f"prefill {out['prefill_s']:.2f}s, decode {out['decode_s']:.2f}s"
          f" -> {out['tok_per_s']:.1f} tok/s (reduced cfg, CPU)")
    for i, row in enumerate(out["generated"][:3]):
        print(f"req {i}: {row.tolist()}")


if __name__ == "__main__":
    main()
