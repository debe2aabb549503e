# Copyright 2026 tiny-deepspeed-tpu authors
# SPDX-License-Identifier: Apache-2.0

"""Sampling entry point: load (or init) a model and generate tokens.

The reference has no inference path at all (its GPT2Model only trains,
reference example/model.py:139-157); `GPT2Model.generate` is the
fixed-shape lax.fori_loop decode this script exposes — one shared
sampling core (models/sampling.py) with the serving tier, so the knobs
here mean exactly what serve_bench's do.  Pairs with the training entry
points' `--checkpoint-dir` checkpoints.

Prompts, most-specific wins:
  --prompt "some text"    tokenized with --tokenizer (byte needs no
                          files; gpt2 needs the local HF cache)
  --prompt-tokens 1,2,3   explicit token ids
  --prompt-len N          N random tokens (decode-path demo, default)
"""

import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), ".."))

import jax
import jax.numpy as jnp


def main():
    p = argparse.ArgumentParser()
    from tiny_deepspeed_tpu.models import ALL_PRESETS
    p.add_argument("--model", default="tiny", choices=sorted(ALL_PRESETS))
    p.add_argument("--ckpt", default=None, metavar="DIR",
                   help="checkpoint dir from --checkpoint-dir (default: "
                        "fresh random init — demonstrates the decode "
                        "path)")
    p.add_argument("--prompt", default=None, metavar="TEXT",
                   help="prompt text, tokenized with --tokenizer")
    p.add_argument("--prompt-tokens", default=None, metavar="IDS",
                   help="comma-separated explicit prompt token ids")
    p.add_argument("--tokenizer", default="byte",
                   choices=("byte", "gpt2"),
                   help="for --prompt, and for rendering outputs as "
                        "text (data/tokenizer.py — the same ids "
                        "prepare_data.py builds training .bins with)")
    p.add_argument("--prompt-len", type=int, default=8,
                   help="random-token prompt length when neither "
                        "--prompt nor --prompt-tokens is given")
    p.add_argument("--max-new-tokens", type=int, default=32)
    p.add_argument("--temperature", type=float, default=0.8)
    p.add_argument("--top-k", type=int, default=50)
    p.add_argument("--batch", type=int, default=2)
    p.add_argument("--seed", type=int, default=0)
    p.add_argument("--cpu", action="store_true",
                   help="run on the CPU backend (without it the TPU is "
                        "required)")
    p.add_argument("--no-cache", action="store_true",
                   help="decode with the full forward per token instead "
                        "of the KV cache (cross-check / debugging; "
                        "greedy outputs match the cached path)")
    args = p.parse_args()

    from tiny_deepspeed_tpu.utils.startup import select_platform
    select_platform(cpu=args.cpu, cpu_flag="--cpu")

    from tiny_deepspeed_tpu import SGD, SingleDevice
    from tiny_deepspeed_tpu.models import build_model

    model = build_model(args.model)
    cfg = model.config

    if args.ckpt:
        from tiny_deepspeed_tpu.utils.checkpoint import load_checkpoint
        engine = SingleDevice(model, SGD(lr=0.0))
        params = load_checkpoint(args.ckpt, engine).params
        print(f"loaded params from {args.ckpt}")
    else:
        params = model.init(jax.random.PRNGKey(args.seed))
        print("fresh random init (pass --ckpt for trained weights)")

    text_mode = False
    if args.prompt is not None and args.prompt_tokens is not None:
        raise SystemExit("--prompt and --prompt-tokens are exclusive")
    if args.prompt is not None:
        from tiny_deepspeed_tpu.data import tokenizer as tok
        try:
            ids = tok.encode(args.prompt, args.tokenizer)
        except RuntimeError as e:
            raise SystemExit(str(e))
        if len(ids) == 0:
            raise SystemExit("--prompt encoded to zero tokens")
        if tok.min_vocab(args.tokenizer) > cfg.vocab_size:
            raise SystemExit(
                f"--tokenizer {args.tokenizer} needs vocab_size >= "
                f"{tok.min_vocab(args.tokenizer)}; model {args.model} "
                f"has {cfg.vocab_size}"
            )
        text_mode = True
    elif args.prompt_tokens is not None:
        import numpy as np
        try:
            ids = np.asarray(
                [int(x) for x in args.prompt_tokens.split(",")], np.int32)
        except ValueError:
            raise SystemExit(
                "--prompt-tokens must be a comma-separated list of ints"
            )
        if ids.size == 0 or ids.min() < 0 or ids.max() >= cfg.vocab_size:
            raise SystemExit(
                f"--prompt-tokens ids must be in [0, {cfg.vocab_size})"
            )
    else:
        ids = None

    if ids is not None:
        prompt = jnp.broadcast_to(
            jnp.asarray(ids, jnp.int32)[None, :],
            (args.batch, len(ids)),
        )
    else:
        prompt = jax.random.randint(
            jax.random.PRNGKey(args.seed),
            (args.batch, args.prompt_len), 0, cfg.vocab_size, jnp.int32,
        )
    t0_len = prompt.shape[1]
    if t0_len + args.max_new_tokens > cfg.block_size:
        raise SystemExit(
            f"prompt {t0_len} + new {args.max_new_tokens} tokens > "
            f"model context {cfg.block_size}"
        )

    import time
    gen = lambda: model.generate(  # noqa: E731
        params, prompt, args.max_new_tokens,
        temperature=args.temperature, top_k=args.top_k,
        key=jax.random.PRNGKey(args.seed + 1),
        use_cache=not args.no_cache,
    )
    out = gen()  # first call compiles
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    out = gen()
    jax.block_until_ready(out)
    dt = time.perf_counter() - t0
    for row in out:
        toks = [int(t) for t in row]
        if text_mode:
            from tiny_deepspeed_tpu.data import tokenizer as tok
            print(f"{args.prompt!r} -> "
                  f"{tok.decode(toks[t0_len:], args.tokenizer)!r}")
        else:
            print(f"prompt={toks[:t0_len]} -> "
                  f"generated={toks[t0_len:]}")
    n = args.batch * args.max_new_tokens
    print(f"decode ({'full forward' if args.no_cache else 'KV cache'}): "
          f"{n / dt:.0f} tokens/s")


if __name__ == "__main__":
    main()
