"""Serving launcher.

    PYTHONPATH=src python -m repro.launch.serve --arch olmo_1b \
        --ckpt /tmp/ck_olmo --batch 4 --new-tokens 16

Restores params from the newest checkpoint (random init without --ckpt)
and serves a batch of synthetic prompts through the Engine. The arch's
published config is the default; --smoke selects its reduced
same-family config for CPU runs.
"""
import argparse

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import ARCH_IDS, get_config
from repro.launch.compile_cache import enable_compile_cache
from repro.models import model_zoo
from repro.serve.engine import Engine, ServeConfig
from repro.train import checkpoint as ckpt_lib

# Engine (KV cache, prefill + per-token decode) and the uncached forward
# compute the same function in different orders, both in bf16. bf16 keeps
# 8 significant bits; logits of a random-init model are O(1) with maxima
# near 5, where one bf16 ulp is 2^-5 = 0.031. The max bound allows 8 such
# ulps carried through 16 layers. The mean bound catches faults the max
# bound can miss, such as one stale cache slot among 128 that attention
# dilutes: on one TPU v5e, olmo_1b at 4x(128+32) tokens read mean 0.0112
# correct, 0.0287 with one prompt slot zeroed, 0.0725 with the cache one
# position behind.
LOGIT_MAX_BOUND = 0.25
LOGIT_MEAN_BOUND = 0.02


def parse_args(argv=None) -> argparse.Namespace:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo_1b", choices=list(ARCH_IDS))
    ap.add_argument("--smoke", action="store_true",
                    help="reduced same-family config (CPU-test scale)")
    ap.add_argument("--ckpt", default=None)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--prompt-len", type=int, default=16)
    ap.add_argument("--new-tokens", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    return ap.parse_args(argv)


def build_engine(args: argparse.Namespace) -> Engine:
    """Config, params (checkpoint or seeded random init) and the Engine."""
    cfg = get_config(args.arch, smoke=args.smoke)
    params = jax.jit(lambda k: model_zoo.init_params(cfg, k))(
        jax.random.PRNGKey(0))
    if args.ckpt:
        res = ckpt_lib.restore(args.ckpt, {"params": jax.eval_shape(
            lambda: params)})
        if res:
            params = res[1]["params"]
            print(f"restored checkpoint step {res[0]}")
    return Engine(cfg, params, scfg=ServeConfig(
        max_seq=args.prompt_len + args.new_tokens + 1,
        max_new_tokens=args.new_tokens, temperature=args.temperature))


def make_prompts(eng: Engine, args: argparse.Namespace) -> np.ndarray:
    """Seeded synthetic prompts [batch, prompt_len] int32."""
    return np.random.RandomState(0).randint(
        0, eng.cfg.vocab, (args.batch, args.prompt_len)).astype(np.int32)


def check_against_forward(eng: Engine, prompts: np.ndarray,
                          toks: np.ndarray, logits) -> dict:
    """Compares the logits ``Engine.generate_with_logits`` emitted with a
    teacher-forced uncached ``model_zoo.forward`` over prompt + emitted
    tokens: its logits at position prompt_len-1+i predicted token i.
    Returns the max and mean |Δlogit| over [B, T, vocab], the share of
    emitted tokens that are the reference's argmax, and ``ok`` under
    LOGIT_MAX_BOUND and LOGIT_MEAN_BOUND."""
    cfg = eng.cfg
    seq = np.concatenate([prompts, toks[:, :-1]], axis=1)
    forward = jax.jit(
        lambda p, t: model_zoo.forward(cfg, p, {"tokens": t})[0])
    ref = forward(eng.params, seq)[:, prompts.shape[1] - 1:, :cfg.vocab]
    ref = ref.astype(jnp.float32)
    got = logits[..., :cfg.vocab].astype(jnp.float32)
    delta = jnp.abs(got - ref)
    out = {"finite": bool(jnp.all(jnp.isfinite(got))),
           "max_abs_logit": float(jnp.max(jnp.abs(ref))),
           "max_abs_dlogit": float(jnp.max(delta)),
           "mean_abs_dlogit": float(jnp.mean(delta)),
           "argmax_agreement": float(jnp.mean(jnp.argmax(ref, -1) == toks))}
    out["ok"] = (out["finite"] and out["max_abs_dlogit"] <= LOGIT_MAX_BOUND
                 and out["mean_abs_dlogit"] <= LOGIT_MEAN_BOUND)
    return out


def main(argv=None):
    args = parse_args(argv)
    enable_compile_cache()
    eng = build_engine(args)
    out = eng.generate(make_prompts(eng, args))
    for i, row in enumerate(out):
        print(f"seq{i}: {row.tolist()}")


if __name__ == "__main__":
    main()
