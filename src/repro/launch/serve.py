"""Serving drivers: LM decode on a mesh, or the LKGP curve service.

LM mode (default; batched prefill + greedy decode)::

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv6_1b6 --smoke \
        --batch 8 --prompt-len 32 --gen 32

uses the serve-optimized sharding rules (weights resident; see
DESIGN.md §6.5): prefill emits the natural cache layout and the decode
loop runs with donated caches.

Curve-prediction mode drives :class:`repro.serving.PredictionService` —
multi-tenant streaming observes with warm refits, coalesced predictions::

    PYTHONPATH=src python -m repro.launch.serve --service curves \
        --tenants 8 --rounds 4
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from ..configs import get_config, get_smoke_config
from ..models import build_model
from ..train.trainer import make_serve_steps
from .compile_cache import use_compile_cache
from .train import make_mesh_from_args


def main_curves(args):
    """Streaming LKGP curve-service driver (synthetic tenants)."""
    from ..core import LKGPConfig
    from ..data.curves import sample_task
    from ..serving import PredictionService, ServiceConfig

    svc = PredictionService(ServiceConfig(
        gp=LKGPConfig(lbfgs_iters=args.lbfgs_iters, backend="dense"),
        capacity=max(args.tenants, 1),
        refit_every=args.refit_every))
    tasks = {f"tenant-{i}": sample_task(args.seed + i, n=args.n, m=args.m,
                                        d=4)
             for i in range(args.tenants)}

    # Cold fits, coalesced across tenants into one vmapped L-BFGS.
    svc.observe_batch([
        dict(tenant=name, task="run", X=task.X, t=task.t,
             Y=task.Y, mask=task.mask)
        for name, task in tasks.items()])

    masks = {name: np.asarray(task.mask).copy()
             for name, task in tasks.items()}
    for rnd in range(args.rounds):
        for name, task in tasks.items():   # reveal one more epoch per curve
            mask = masks[name]
            for i in range(mask.shape[0]):
                k = int(mask[i].sum())      # lint: disable=RA103
                if k < mask.shape[1]:
                    mask[i, k] = 1.0
            Y = np.where(mask > 0,
                         np.asarray(task.Y_full),    # lint: disable=RA103
                         0.0)
            svc.observe(name, "run", Y, mask)
        preds = svc.predict_many([(name, "run") for name in tasks])
        # Prediction.mean is host numpy already — no device sync here.
        best = {p.tenant: float(np.max(p.mean))      # lint: disable=RA103
                for p in preds}
        print(f"round {rnd}: coalesced batch={preds[0].batch_size} "
              f"best-final={max(best.values()):.4f}")

    # Per-request repeats ride the warm state-keyed posterior cache.
    t0 = time.time()
    for name in tasks:
        svc.predict(name, "run")
    print(f"warm per-request sweep: "
          f"{(time.time() - t0) / max(len(tasks), 1) * 1e3:.2f} ms/req")
    m = svc.metrics()
    print(f"store={m['store']} counters={m['counters']}")
    print(f"predict p50={m['predict_latency']['p50_ms']:.2f} ms "
          f"p99={m['predict_latency']['p99_ms']:.2f} ms")
    return m


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--service", default="lm", choices=["lm", "curves"],
                    help="lm: decode loop (default); curves: LKGP service")
    ap.add_argument("--arch")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=32)
    ap.add_argument("--mesh", default="debug",
                    choices=["debug", "single", "multi"])
    ap.add_argument("--seed", type=int, default=0)
    # curve-service knobs
    ap.add_argument("--tenants", type=int, default=8)
    ap.add_argument("--rounds", type=int, default=3)
    ap.add_argument("--n", type=int, default=8)
    ap.add_argument("--m", type=int, default=10)
    ap.add_argument("--refit-every", type=int, default=4)
    ap.add_argument("--lbfgs-iters", type=int, default=10)
    args = ap.parse_args(argv)
    use_compile_cache()

    if args.service == "curves":
        return main_curves(args)
    if args.arch is None:
        ap.error("--arch is required for --service lm")

    cfg = get_smoke_config(args.arch) if args.smoke else get_config(args.arch)
    model = build_model(cfg)
    mesh = make_mesh_from_args(args)
    # Only VLM configs carry patch tokens; anything else (including ad-hoc
    # config objects) contributes 0 to the cache length.
    num_patch = getattr(cfg, "num_patch_tokens", 0) or 0
    serve = make_serve_steps(model, mesh,
                             max_len=args.prompt_len + args.gen + num_patch)
    with mesh:
        params = jax.jit(model.init,
                         out_shardings=serve["param_shardings"])(
                             jax.random.key(args.seed))
        batch = {"tokens": jax.random.randint(
            jax.random.PRNGKey(args.seed + 1),
            (args.batch, args.prompt_len), 0, cfg.vocab_size)}
        if cfg.family in ("audio", "encdec"):
            batch["frames"] = jnp.zeros(
                (args.batch, cfg.enc_frames, cfg.d_model), jnp.float32)
        if cfg.family == "vlm":
            batch["prefix_embeds"] = jnp.zeros(
                (args.batch, cfg.num_patch_tokens, cfg.d_model), jnp.float32)

        t0 = time.time()
        logits, cache = jax.jit(serve["prefill"])(params, batch)
        jax.block_until_ready(logits)
        t_prefill = time.time() - t0

        step = jax.jit(serve["decode_step"], donate_argnums=(1,))
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        out = [tok]
        t0 = time.time()
        for _ in range(args.gen - 1):
            logits, cache = step(params, cache, tok)
            tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
            out.append(tok)
        jax.block_until_ready(logits)
        t_decode = time.time() - t0

    gen = np.asarray(jnp.concatenate(out, axis=1))
    print(f"arch={args.arch} batch={args.batch} prompt={args.prompt_len} "
          f"generated={gen.shape[1]}")
    print(f"prefill: {t_prefill*1e3:.1f} ms; decode: "
          f"{t_decode/max(args.gen-1,1)*1e3:.1f} ms/token "
          f"({args.batch*(args.gen-1)/max(t_decode,1e-9):.0f} tok/s)")
    for i in range(min(2, args.batch)):
        print(f"  req {i}: {gen[i, :10].tolist()} ...")
    return gen


if __name__ == "__main__":
    main()
