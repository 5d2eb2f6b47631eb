"""repro: Latent Kronecker GPs for learning-curve prediction, production JAX.

Layout:
  repro.core        — the paper's model (LKGP) and its linear algebra
  repro.kernels     — Pallas TPU kernel: the masked latent-Kronecker MVM
  repro.models      — the 10 assigned LM architectures (pure JAX)
  repro.configs     — published configs + reduced smoke variants
  repro.data        — learning-curve prior + token pipeline
  repro.train       — optimizers, train/serve step builders
  repro.distributed — sharding rules, collectives, distributed LKGP
  repro.checkpoint  — fault-tolerant checkpoint manager
  repro.autotune    — LKGP-driven early-stopping scheduler
  repro.baselines   — amortized transformer baseline + head-to-head eval
  repro.amortize    — hyper-parameter amortizer (warm starts for fit/refit)
  repro.launch      — production meshes, multi-pod dry-run, roofline
"""
__version__ = "1.0.0"
