"""PTQ calibration launcher — AffineQuant and every baseline from one CLI.

    PYTHONPATH=src python -m repro.launch.calibrate --arch llama-mini \
        --ckpt checkpoints/llama-mini --method affine --wbits 3 --group 64

Methods: affine (the paper) | omniquant (diag-only) | rtn | awq | gptq.
Outputs a quantized checkpoint + a JSON report (per-block losses, final
eval perplexity on held-out synthetic data).
"""
from __future__ import annotations

import argparse
import json
from pathlib import Path

import jax
import jax.numpy as jnp

from repro.configs import get_config
from repro.core.baselines import quantize_model_baseline
from repro.core.calibration import CalibConfig, quantize_dense_model
from repro.core.quantizer import QuantConfig
from repro.data import MarkovCorpus
from repro.models import build_model
from repro.train import checkpoints
from repro.utils import logger


def eval_ppl(model, params, tokens) -> float:
    return float(jnp.exp(model.loss(params, {"tokens": tokens})))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama-mini")
    ap.add_argument("--ckpt", default=None,
                    help="checkpoint dir of a trained model (optional)")
    ap.add_argument("--method", default="affine",
                    choices=["affine", "omniquant", "rtn", "awq", "gptq"])
    ap.add_argument("--wbits", type=int, default=4)
    ap.add_argument("--abits", type=int, default=16)
    ap.add_argument("--group", type=int, default=0)
    ap.add_argument("--epochs", type=int, default=10)
    ap.add_argument("--alpha", type=float, default=0.1)
    ap.add_argument("--calib-samples", type=int, default=16)
    ap.add_argument("--calib-seq", type=int, default=128)
    ap.add_argument("--out", default="quantized")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    cfg = get_config(args.arch)
    model = build_model(cfg)
    params = model.init(jax.random.PRNGKey(args.seed))
    if args.ckpt:
        from repro.optim import AdamConfig
        from repro.train.step import init_train_state
        state = init_train_state(model, jax.random.PRNGKey(args.seed),
                                 AdamConfig())
        state, step = checkpoints.restore(args.ckpt, state)
        params = state.params
        logger.info("loaded checkpoint step %d", step)

    corpus = MarkovCorpus(vocab=cfg.vocab_size, seed=args.seed)
    calib = jnp.asarray(corpus.sample(args.calib_samples, args.calib_seq,
                                      seed=777))
    test = jnp.asarray(corpus.sample(16, args.calib_seq, seed=999))

    qcfg = QuantConfig(w_bits=args.wbits, a_bits=args.abits,
                       group_size=args.group,
                       lwc=args.method in ("affine", "omniquant"))
    info: dict = {"method": args.method, "config": qcfg.tag(),
                  "fp_ppl": eval_ppl(model, params, test)}

    if args.method in ("affine", "omniquant"):
        ccfg = CalibConfig(epochs=args.epochs, alpha=args.alpha,
                           use_affine=args.method == "affine")
        qparams, cal_info = quantize_dense_model(params, cfg, qcfg, ccfg,
                                                 calib)
        info["block_final_losses"] = cal_info["final_losses"]
    else:
        qparams = quantize_model_baseline(params, cfg, qcfg, calib,
                                          args.method)

    info["quant_ppl"] = eval_ppl(model, qparams, test)
    logger.info("%s %s: fp ppl %.3f -> quant ppl %.3f", args.method,
                qcfg.tag(), info["fp_ppl"], info["quant_ppl"])

    out = Path(args.out)
    out.mkdir(parents=True, exist_ok=True)
    checkpoints.save(out / f"{args.arch}-{args.method}-{qcfg.tag()}", 0,
                     qparams)
    (out / f"{args.arch}-{args.method}-{qcfg.tag()}.json").write_text(
        json.dumps(info, indent=2))
    return 0


if __name__ == "__main__":
    from repro.utils import enable_compile_cache
    enable_compile_cache()
    raise SystemExit(main())
