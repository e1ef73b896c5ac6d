"""End-to-end driver: train the paper's IMDB sentiment SNN (Fig. 9b/10).

Architecture: GloVe-100d words -> encoder(100) -> FC128 -> FC128 -> 1 readout,
RMP neurons, 6-bit QAT weights, 11-bit V_MEM, 10 timesteps/word, membrane
state persists across words (the paper's sequential-memory mechanism).
29,312 trainable weights (paper: 29.3K).

Uses the real IMDB+GloVe if present on disk (data/imdb.py), else the
structure-matched synthetic task. A few hundred steps trains to >85% on the
synthetic task in a few minutes on CPU.

    PYTHONPATH=src python examples/train_sentiment_snn.py --steps 300
    PYTHONPATH=src python examples/train_sentiment_snn.py --trace   # Fig. 10
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.impulse_snn import IMDB
from repro.core import energy, pipeline, snn
from repro.data import imdb, make_sentiment_vocab, sentiment_batch
from repro.optim import adamw, apply_updates


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--words", type=int, default=12)
    ap.add_argument("--lr", type=float, default=2e-3)
    ap.add_argument("--trace", action="store_true", help="print Fig.10-style V trace")
    ap.add_argument("--backend", default="int_ref",
                    choices=["int_ref", "pallas"],
                    help="integer backend for the deployed-program eval")
    ap.add_argument("--interpret", action="store_true",
                    help="Pallas interpret mode (CPU containers)")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    use_real = imdb.available()
    print(f"data: {'real IMDB+GloVe' if use_real else 'synthetic (structure-matched)'}")
    ds = None if use_real else make_sentiment_vocab(args.seed)
    if use_real:
        glove = imdb.load_glove()
        xs_all, ys_all = imdb.vectorize(imdb.load_reviews("train", 2000), glove,
                                        args.words)

    params = snn.init_fc_snn(jax.random.PRNGKey(args.seed), IMDB)
    print(f"trainable params: {snn.param_count(params)} (paper: 29.3K); "
          f"LSTM baseline: 247.8K (8.5x)")
    opt = adamw(lambda s: args.lr, weight_decay=0.0)
    opt_state = opt.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        (loss, aux), g = jax.value_and_grad(snn.sentiment_loss, has_aux=True)(
            params, x, y, IMDB)
        upd, opt_state = opt.update(g, opt_state, params)
        return apply_updates(params, upd), opt_state, loss, aux["accuracy"]

    t0 = time.time()
    for s in range(args.steps):
        if use_real:
            idx = np.random.default_rng(s).integers(0, len(xs_all), args.batch)
            x, y = jnp.asarray(xs_all[idx]), jnp.asarray(ys_all[idx])
        else:
            xb, yb = sentiment_batch(ds, args.batch, args.words, seed=s)
            x, y = jnp.asarray(xb), jnp.asarray(yb)
        params, opt_state, loss, acc = step(params, opt_state, x, y)
        if (s + 1) % 25 == 0 or s == 0:
            print(f"step {s+1:4d}  loss {float(loss):.4f}  acc {float(acc):.3f}"
                  f"  ({time.time()-t0:.0f}s)")

    # ---- eval: float QAT path vs deployed integer (macro) path ----
    xb, yb = sentiment_batch(ds, 512, args.words, seed=10_001) if not use_real \
        else (xs_all[:512], ys_all[:512])
    x, y = jnp.asarray(xb), jnp.asarray(yb)
    logits, _ = snn.sentiment_apply(params, x, IMDB)
    acc_f = float(jnp.mean((logits > 0) == (y > 0.5)))
    # deployed program: compile once, run on the chosen integer backend
    program = pipeline.compile_network(IMDB, params, domain="int")
    xs = pipeline.present_words(x, IMDB.timesteps)
    bkw = ({"interpret": True}
           if args.backend == "pallas" and args.interpret else {})
    res = pipeline.run_network(program, xs, args.backend, **bkw)
    logits_i, rasters = res.logits[:, 0], res.rasters
    counts = pipeline.count_network_instructions(program, rasters)
    acc_i = float(jnp.mean((logits_i > 0) == (y > 0.5)))
    agree = float(jnp.mean((logits_i > 0) == (logits > 0)))
    print(f"\neval accuracy: float/QAT={acc_f:.4f}  "
          f"int-macro[{args.backend}]={acc_i:.4f} (agreement {agree:.3f})")

    sparsities = [1.0 - float(np.asarray(r).mean()) for r in rasters]
    print("per-layer spike sparsity (Fig.11a):",
          [f"{s:.3f}" for s in sparsities])
    e = energy.snn_energy_j(counts)
    n_inf = x.shape[0]
    print(f"macro energy for {n_inf} inferences: {e*1e9:.2f} nJ "
          f"({e/n_inf*1e12:.1f} pJ/inference) at point D")

    if args.trace:
        logits, aux = snn.sentiment_apply(params, x[:2], IMDB, return_trace=True)
        tr = np.asarray(aux["v_trace"])                      # (T_total, 2)
        print("\nFig.10 membrane trace (output neuron V per timestep):")
        for b in range(2):
            lab = "positive" if float(y[b]) > 0.5 else "negative"
            line = " ".join(f"{v:+.1f}" for v in tr[:: IMDB.timesteps, b])
            print(f"  true={lab:8s} V/word: {line}")
    return acc_f, acc_i


if __name__ == "__main__":
    main()
