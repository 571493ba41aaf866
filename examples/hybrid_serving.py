"""Hybrid-query-augmented serving: the paper's technique in the LM stack.

A qwen2-style model serves batched requests; before decoding, each request
runs a CHASE VKNN-SF query (similarity + freshness + safety filters) over a
document corpus, and the retrieved doc tokens are prepended (RAG).

The retriever rides the session API end to end: one Database session, one
prepared Statement (plan-cached), batched retrieval through the
size-bucketed executor, and an async submit/poll server from ``db.serve``.

  PYTHONPATH=src python examples/hybrid_serving.py
"""
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np
import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.configs import get_config
from repro.models import init_params
from repro.serving.decode import generate
from repro.serving.rag import HybridRetriever


def main():
    cfg = get_config("qwen2-1.5b", smoke=True)
    params = init_params(jax.random.key(0), cfg)

    # document corpus with structured metadata
    rng = np.random.default_rng(0)
    n_docs = 5000
    docs = rng.standard_normal((n_docs, cfg.d_model)).astype(np.float32)
    docs /= np.linalg.norm(docs, axis=1, keepdims=True)
    freshness = rng.random(n_docs).astype(np.float32)
    safety = rng.integers(0, 4, n_docs).astype(np.int32)
    retriever = HybridRetriever.build(
        jnp.asarray(docs), jnp.asarray(freshness), jnp.asarray(safety), k=4)
    print(f"retriever over {n_docs} docs (CHASE VKNN-SF, fused filters)")
    print(retriever.statement.explain())

    # batched requests
    batch, prompt_len = 4, 12
    prompts = jax.random.randint(jax.random.key(1), (batch, prompt_len), 0,
                                 cfg.vocab_size, dtype=jnp.int32)
    # query embeddings from mean prompt embedding (stub encoder)
    qemb = jnp.mean(params["embed"][prompts].astype(jnp.float32), axis=1)
    qemb = qemb / (jnp.linalg.norm(qemb, axis=-1, keepdims=True) + 1e-6)

    t0 = time.perf_counter()
    ids, sims, valid = retriever.retrieve_batch(np.asarray(qemb),
                                                min_freshness=0.3,
                                                safety_class=0)
    print(f"\nretrieved (k=4 docs/request, freshness>=0.3, safety=0) "
          f"in {(time.perf_counter()-t0)*1e3:.1f} ms:")
    for b in range(batch):
        print(f"  request {b}: docs={np.asarray(ids)[b].tolist()} "
              f"sims={np.round(np.asarray(sims)[b], 3).tolist()}")
    # check filters held
    got = np.asarray(ids)[np.asarray(valid)]
    assert (freshness[got] >= 0.3).all() and (safety[got] == 0).all()

    # async serving front-end: db.serve wraps the BatchScheduler over the
    # SAME prepared statement (shared plan-cache entry + bucket executables)
    server = retriever.db.serve(retriever.statement, max_batch=8,
                                max_wait_ms=0.0)
    rids = [server.submit(query_embedding=q, min_freshness=0.3,
                          safety_class=0) for q in qemb]
    server.flush()
    sched_ids = np.stack([np.asarray(server.result(r)["ids"]) for r in rids])
    assert np.array_equal(sched_ids, np.asarray(ids))
    print("async submit/poll through db.serve matches direct batch  [ok]")

    doc_tokens = (np.asarray(ids) * 7919 % cfg.vocab_size).astype(np.int32)
    prefix = jnp.concatenate([jnp.asarray(doc_tokens), prompts], axis=1)
    t0 = time.perf_counter()
    out = generate(params, cfg, prefix, 16)
    out = jax.block_until_ready(out)
    print(f"\ngenerated 16 tokens/request in "
          f"{time.perf_counter()-t0:.1f}s (incl. compile)")
    print(np.asarray(out))


if __name__ == "__main__":
    enable_compile_cache()
    main()
