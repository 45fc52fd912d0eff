"""The 2026 production pipeline: LM embeddings -> the paper's clustering.

    PYTHONPATH=src python examples/embed_and_cluster.py --arch rwkv6-3b

Documents from the synthetic topic corpus are rendered as token sequences,
embedded with a (reduced-config) model from the zoo via mean-pooled hidden
states, and clustered with Buckshot. Compares clustering quality of
LM embeddings vs raw tf-idf on the same documents — the framework's two
first-class document representations (DESIGN.md §3).
"""

import argparse

import jax
import jax.numpy as jnp
import numpy as np


def tokens_from_counts(counts: np.ndarray, vocab: int, seq: int, seed: int):
    """Render bag-of-words counts as pseudo token sequences (offline stand-in
    for a tokenizer: sample tokens proportional to counts)."""
    rng = np.random.default_rng(seed)
    n, _ = counts.shape
    out = np.zeros((n, seq), np.int32)
    for i in range(n):
        p = counts[i] / max(counts[i].sum(), 1.0)
        out[i] = rng.choice(len(p), size=seq, p=p)
    return out % vocab


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2-1.5b")
    ap.add_argument("--n", type=int, default=512)
    ap.add_argument("--k", type=int, default=8)
    ap.add_argument("--seq", type=int, default=64)
    args = ap.parse_args()

    from repro.common import l2_normalize, use_compile_cache
    from repro.configs import get_config
    from repro.core import buckshot, metrics
    from repro.models.registry import get_model
    from repro.serve.engine import ServeEngine
    from repro.text import synth, tfidf

    use_compile_cache()
    corpus = synth.make_corpus(args.n, vocab=512, n_topics=args.k, seed=1)
    labels = jnp.asarray(corpus.labels)
    key = jax.random.PRNGKey(0)

    # ---- representation 1: tf-idf (the paper's)
    x_tfidf = tfidf.tfidf(jnp.asarray(corpus.counts))
    bs = buckshot(x_tfidf, args.k, key, kmeans_iters=2)
    pur = float(metrics.purity(bs.kmeans.assignment, labels, args.k, args.k))
    nmi = float(metrics.nmi(bs.kmeans.assignment, labels, args.k, args.k))
    print(f"tf-idf   + Buckshot: purity={pur:.3f} nmi={nmi:.3f}")

    # ---- representation 2: LM embeddings (mean-pooled hidden states)
    cfg = get_config(args.arch, reduced=True)
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(1))
    engine = ServeEngine(cfg=cfg, params=params)

    toks = tokens_from_counts(corpus.counts, cfg.vocab, args.seq, seed=2)
    embeds = []
    bs_sz = 64
    for i in range(0, args.n, bs_sz):
        batch = {"tokens": jnp.asarray(toks[i : i + bs_sz])}
        if cfg.family in ("vlm", "encdec"):
            batch["frontend"] = jnp.zeros(
                (batch["tokens"].shape[0], cfg.n_frontend_tokens, cfg.frontend_dim),
                jnp.float32,
            )
        embeds.append(np.asarray(engine.embed(batch)))
    x_lm = l2_normalize(jnp.asarray(np.concatenate(embeds)))

    bs2 = buckshot(x_lm, args.k, key, kmeans_iters=2)
    pur2 = float(metrics.purity(bs2.kmeans.assignment, labels, args.k, args.k))
    nmi2 = float(metrics.nmi(bs2.kmeans.assignment, labels, args.k, args.k))
    print(f"{args.arch:8s} + Buckshot: purity={pur2:.3f} nmi={nmi2:.3f} "
          f"(untrained reduced model — structure only)")
    print("\nsame clustering core, two representations; on a real pod the "
          "embed step is the sharded prefill path certified by the dry-run.")


if __name__ == "__main__":
    main()
