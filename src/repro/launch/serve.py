"""Serving launcher: batched generation with KV caches, plus an
uncertainty-aware endpoint backed by a last-layer Laplace posterior.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        --batch 4 --prompt-len 8 --max-len 64

    # next-token mean + predictive variance instead of sampled tokens:
    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6-3b \
        --batch 4 --prompt-len 8 --uncertainty
"""
import argparse

import jax
import jax.numpy as jnp

from repro.launch.cache import enable_compile_cache
from repro.configs import get_config
from repro.nn.models import build_model
from repro.serve.engine import ServeConfig, generate, generate_whisper


def serve_uncertainty(cfg, model, params, prompts, *,
                      marglik_steps=25, seed=0, top_k=5, log_fn=print):
    """Uncertainty-aware endpoint: next-token logit mean + variance.

    Fits a last-layer **diagonal** Laplace posterior on one deterministic
    calibration batch — the only structure that scales to LM heads: its
    state is O(d·V) where the Kronecker B factor would be a dense [V, V]
    (plus an O(V³) eigendecomposition), and the MC sweep (DiagGGNMC) keeps
    the curvature pass at one gradient-like sweep where the exact factor's
    leading axis is T·V.  Prior precision is tuned by evidence ascent;
    predictions use the rank-1 closed-form GLM for the final prompt
    position (no Jacobian seed materialized — see
    ``laplace.predictive._dense_glm_closed_form``).
    """
    from repro import laplace
    from repro.core import CrossEntropyLoss, ExtensionConfig
    from repro.data.synthetic import DataConfig, lm_batch
    from repro.laplace.posterior import split_last_dense

    loss = CrossEntropyLoss()
    dc = DataConfig(vocab=cfg.vocab, seq_len=prompts.shape[1],
                    global_batch=prompts.shape[0], seed=seed)
    calib = lm_batch(dc, 0)
    post = laplace.fit_posterior(
        model, params, calib["inputs"], calib["labels"], loss,
        structure="diag", last_layer=True,
        options=laplace.FitOptions(mc=True,
                                   cfg=ExtensionConfig(mc_seed=seed)))
    post, res = laplace.optimize_marglik(post, n_steps=marglik_steps)
    log_fn(f"[laplace] log-evidence {float(laplace.log_marglik(post)):.1f} "
           f"prior_prec {res.prior_prec:.3g}")

    feats, head, f_params, h_params = split_last_dense(model, params)
    phi = feats.apply(f_params, prompts)          # [N, T, d]
    mean, var = laplace.glm_predictive(
        head, h_params, post.inner, phi[:, -1])   # final position: [N, V]
    probs = laplace.probit_predictive(mean, var)
    for n in range(min(2, mean.shape[0])):
        order = jnp.argsort(-mean[n])[:top_k]
        row = " ".join(
            f"tok{int(t)}:{float(mean[n, t]):.2f}±"
            f"{float(jnp.sqrt(var[n, t])):.2f}"
            for t in order)
        log_fn(f"  prompt {n}: {row}")
    return mean, var, probs


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--full", action="store_true")
    ap.add_argument("--uncertainty", action="store_true",
                    help="next-token mean + Laplace predictive variance "
                         "instead of sampled tokens")
    args = ap.parse_args()
    enable_compile_cache()

    cfg = get_config(args.arch)
    if not args.full:
        cfg = cfg.reduced()
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    sc = ServeConfig(max_len=args.max_len, temperature=args.temperature)

    if args.uncertainty:
        if cfg.kind == "encdec":
            raise SystemExit("--uncertainty supports decoder-only archs")
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab)
        mean, var, _ = serve_uncertainty(cfg, model, params, prompts)
        print(f"served mean+variance for {mean.shape} next-token logits "
              f"(mean var {float(var.mean()):.4f})")
        return

    if cfg.kind == "encdec":
        frames = jax.random.normal(jax.random.PRNGKey(1),
                                   (args.batch, 64, cfg.d_model))
        toks = generate_whisper(model, params, frames, sc)
    else:
        prompts = jax.random.randint(jax.random.PRNGKey(1),
                                     (args.batch, args.prompt_len), 0,
                                     cfg.vocab)
        toks = generate(model, params, prompts, sc)
    print(f"generated {toks.shape} tokens")
    for row in toks[: min(2, args.batch)]:
        print(" ", " ".join(str(int(t)) for t in row[:24]), "...")


if __name__ == "__main__":
    main()
