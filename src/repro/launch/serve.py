"""Serving launcher: static-batch or continuous-batching generation
against the selected arch, optionally restoring trained params.

    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --ckpt /path/to/checkpoint_dir            # newest verified step
    PYTHONPATH=src python -m repro.launch.serve --arch llama3-8b --smoke \
        --continuous --requests 8                 # paged continuous engine

``--ckpt`` loads params through the checkpoint manifest (newest checkpoint
whose param leaves verify, walking past corrupt ones); without it, params
are freshly initialized.
"""
from __future__ import annotations

import argparse


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3-8b")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--ckpt", default="",
                    help="checkpoint dir: restore newest verified params")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--continuous", action="store_true",
                    help="continuous-batching engine (staggered arrivals)")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--max-slots", type=int, default=4)
    ap.add_argument("--page-size", type=int, default=16)
    args = ap.parse_args()
    from repro.launch.runtime import configure_compile_cache

    configure_compile_cache()  # before jax is imported
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.configs.registry import get_config
    from repro.models import build_model
    from repro.serve.engine import ContinuousEngine, ServeEngine

    cfg = get_config(args.arch, smoke=args.smoke)
    if args.smoke:
        cfg = cfg.with_(dtype=jnp.float32)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    if args.ckpt:
        from repro.train.checkpoint import load_params_latest

        params, step = load_params_latest(args.ckpt, params)
        print(f"[serve] restored params from {args.ckpt} step {step}")
    key = jax.random.PRNGKey(1)

    def prefix_extras(batch_axis: bool, k):
        n = args.batch if batch_axis else None
        if cfg.family == "vlm":
            shape = (8 if args.smoke else cfg.n_patches, cfg.d_model)
            x = jnp.zeros(shape if n is None else (n,) + shape)
            return {"patch_embeds": x}
        if cfg.family == "audio":
            shape = (cfg.enc_frames, cfg.d_model)
            x = jnp.zeros(shape if n is None else (n,) + shape)
            return {"frame_embeds": x}
        return {}

    if args.continuous:
        eng = ContinuousEngine(
            model, params,
            max_slots=args.max_slots,
            max_seq_len=args.prompt_len + args.new_tokens + args.page_size,
            page_size=args.page_size,
        )
        for i in range(args.requests):
            k = jax.random.fold_in(key, i)
            prompt = np.asarray(jax.random.randint(
                k, (args.prompt_len,), 0, cfg.vocab_size))
            ex = {k2: np.asarray(v)
                  for k2, v in prefix_extras(False, k).items()}
            eng.submit(prompt, args.new_tokens, arrival=i,
                       extras=ex or None)
        results = eng.run()
        emitted = sum(len(r.tokens) for r in results.values())
        print(f"[serve] continuous: {len(results)} requests, "
              f"{emitted} tokens in {eng.total_ticks} ticks")
        first = results[min(results)]
        print(first.tokens.tolist())
        return

    batch = {"tokens": jax.random.randint(
        key, (args.batch, args.prompt_len), 0, cfg.vocab_size)}
    batch.update(prefix_extras(True, key))
    eng = ServeEngine(model, params,
                      capacity=args.prompt_len + args.new_tokens + 8)
    out = eng.generate(batch, max_new_tokens=args.new_tokens)
    print(f"[serve] generated {out.tokens.shape}")
    print(out.tokens[0].tolist())


if __name__ == "__main__":
    main()
