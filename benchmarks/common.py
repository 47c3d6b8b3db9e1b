"""Shared benchmark harness: corpus/feedback construction, router zoo,
result persistence."""
from __future__ import annotations

import json
import time
from pathlib import Path
from typing import Dict

import jax
import numpy as np

from repro.core.router import (EagleConfig, EagleRouter, GlobalOnlyRouter,
                               LocalOnlyRouter)
from repro.data.routerbench import (DATASETS, evaluate_router, make_corpus,
                                    pairwise_feedback, winrate_targets)
from repro.routing.baselines import KNNRouter, MLPRouter, SVMRouter

RESULTS = Path(__file__).resolve().parent.parent / "results"

# frozen benchmark regime (see DESIGN.md §7)
N_PER_DATASET = 300
DIM = 64
PAIRS_PER_QUERY = 8
SEEDS = (0, 1, 2, 3, 4)


def build(seed: int, n_per_dataset: int = N_PER_DATASET):
    corpus = make_corpus(seed=seed, n_per_dataset=n_per_dataset, dim=DIM)
    fb = pairwise_feedback(corpus, corpus.train_idx, seed=seed,
                           pairs_per_query=PAIRS_PER_QUERY)
    return corpus, fb


def fit_eagle(corpus, fb, cls=EagleRouter, **cfg_kw):
    cfg = EagleConfig(embed_dim=DIM, **cfg_kw)
    r = cls(corpus.model_names, corpus.costs, cfg, db_capacity=4096)
    secs = r.fit(fb["emb"], fb["model_a"], fb["model_b"], fb["outcome"],
                 query_id=fb["query_idx"])
    return r, secs


def fit_baselines(corpus, fb, regime: str = "online") -> Dict:
    """regime 'online': win-rate targets from the same pairwise feedback
    Eagle sees (the paper's deployment scenario, §1 challenge 2).
    regime 'offline': the full binary quality matrix (RouterBench-style)."""
    out = {}
    if regime == "online":
        emb, tgt, mask = winrate_targets(fb, corpus.n_models)
    else:
        tr = corpus.train_idx
        emb, tgt, mask = corpus.embeddings[tr], corpus.quality[tr], None
    for name, r in (("knn", KNNRouter(corpus.costs)),
                    ("mlp", MLPRouter(corpus.costs)),
                    ("svm", SVMRouter(corpus.costs))):
        secs = r.fit(emb, tgt, mask)
        out[name] = (r, secs)
    return out


def sum_auc(router, corpus) -> float:
    return float(sum(
        evaluate_router(lambda e, b: router.route(e, b), corpus,
                        dataset=d)["auc"]
        for d in range(len(DATASETS))))


def per_dataset_auc(router, corpus):
    return {DATASETS[d]: evaluate_router(
        lambda e, b: router.route(e, b), corpus, dataset=d)["auc"]
        for d in range(len(DATASETS))}


def save_json(name: str, payload) -> Path:
    RESULTS.mkdir(parents=True, exist_ok=True)
    path = RESULTS / name
    path.write_text(json.dumps(payload, indent=1, default=float))
    return path


def timer(fn, *args, repeat: int = 3, **kw):
    """Median wall microseconds per call."""
    ts = []
    for _ in range(repeat):
        t0 = time.perf_counter()
        out = fn(*args, **kw)
        jax.block_until_ready(out)
        ts.append((time.perf_counter() - t0) * 1e6)
    return float(np.median(ts)), out
