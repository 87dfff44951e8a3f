"""Serving driver: continuous-batching generation of QA prompts for a
text-only transformer arch (dense, MoE, MLA), at bf16 serving precision.

  PYTHONPATH=src python -m repro.launch.serve --arch qwen3-4b \\
      --requests 12 --slots 4 --max-new 16

serves the reduced config; ``--no-reduced`` serves the published widths,
which needs an accelerator with room for the weights (qwen3-4b: ~8 GB).
"""

from __future__ import annotations

import argparse
import time

import jax

from repro.configs import get_config
from repro.core.ratelimit import TokenBucket
from repro.data import HashTokenizer, qa_examples
from repro.launch.compile_cache import enable_compile_cache
from repro.models import params as pm
from repro.models.model import build_model, serving_param_specs
from repro.serve import ContinuousBatcher, Request


def main() -> None:
    p = argparse.ArgumentParser()
    p.add_argument("--arch", default="qwen3-4b")
    p.add_argument("--reduced", action=argparse.BooleanOptionalAction,
                   default=True)
    p.add_argument("--requests", type=int, default=12)
    p.add_argument("--slots", type=int, default=4)
    p.add_argument("--max-new", type=int, default=16)
    p.add_argument("--max-len", type=int, default=128)
    p.add_argument("--temperature", type=float, default=0.0)
    p.add_argument("--admission-tpm", type=float, default=0.0,
                   help=">0 enables token-bucket admission control")
    args = p.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    model = build_model(cfg, remat="none")
    params = pm.init_params(
        jax.random.key(0), serving_param_specs(model.param_specs())
    )
    tok = HashTokenizer(cfg.vocab_size)

    admission = None
    if args.admission_tpm > 0:
        bucket = TokenBucket(1e9, args.admission_tpm, 1)
        admission = bucket.acquire

    sched = ContinuousBatcher(
        model, cfg, params,
        n_slots=args.slots, max_len=args.max_len,
        eos_id=tok.eos_id, temperature=args.temperature, admission=admission,
    )
    rows = qa_examples(args.requests, seed=0)
    t0 = time.time()
    for i, row in enumerate(rows):
        toks = tok.encode(row["question"])[: args.max_len // 2]
        sched.submit(Request(i, prompt_tokens=toks, max_new_tokens=args.max_new))
    done = sched.run_to_completion()
    dt = time.time() - t0
    total_new = sum(len(c.tokens) for c in done)
    for c in sorted(done, key=lambda c: c.request_id)[:5]:
        print(f"req {c.request_id}: {len(c.tokens)} tokens ({c.finished_reason}) "
              f"-> {tok.decode(c.tokens)[:60]!r}")
    print(
        f"\n{len(done)} completions, {total_new} new tokens in {dt:.2f}s "
        f"({total_new/dt:.1f} tok/s, {sched.steps_run} scheduler iterations, "
        f"{args.slots} slots)"
    )


if __name__ == "__main__":
    main()
