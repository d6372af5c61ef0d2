"""Serving example: continuous-batching decode engine + PackSELL
pruned-weight linear (the paper's SpMV in the decode path).

    PYTHONPATH=src python examples/serve_sparse.py
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro import configs
from repro.launch.compile_cache import use_compile_cache
from repro.models import transformer as tfm
from repro.models.sparse_linear import PackSELLLinear
from repro.serving import DecodeEngine, ServeConfig


def main():
    use_compile_cache()
    cfg = configs.reduce(configs.get("granite-3-2b"))
    params, _ = tfm.init_params(cfg, jax.random.PRNGKey(0))

    # --- 1) batched serving with continuous batching ---------------------
    eng = DecodeEngine(cfg, params, ServeConfig(slots=4, max_len=96))
    eng.warmup()        # compile the pool decode step before traffic lands
    rng = np.random.default_rng(0)
    for _ in range(8):
        eng.submit(rng.integers(1, cfg.vocab, size=int(rng.integers(4, 12))),
                   max_new_tokens=8)
    done = eng.run()
    st = eng.stats()
    print(f"served {st['requests']} requests, {st['tokens']} tokens, "
          f"{st['tokens_per_s']:.1f} tok/s, "
          f"mean TTFT {st['mean_ttft_s'] * 1e3:.0f} ms")

    # --- 2) PackSELL pruned-weight decode matvec --------------------------
    # decode is memory-bound: bytes-streamed-per-token is the cost. Take the
    # model's largest projection (the LM head) and compare dense bf16
    # streaming vs PackSELL at 30% density with the bf16 embed codec.
    w = np.asarray(params["head"]["w"], np.float32)      # [d, vocab]
    lin = PackSELLLinear.from_dense(w, density=0.3, codec="bf16", D=15,
                                    C=128, sigma=256)
    x = np.asarray(jax.random.normal(jax.random.PRNGKey(1),
                                     (cfg.d_model,)), np.float32)
    y_sparse = lin(jnp.asarray(x))
    y_dense = jnp.asarray(x) @ jnp.asarray(w)
    dense_bf16_bytes = w.size * 2
    sp = lin.decode_bytes_per_token()
    print(f"\nLM head [{w.shape[0]}x{w.shape[1]}]: dense bf16 "
          f"{dense_bf16_bytes:,} B/token vs PackSELL(30%) {sp:,} B/token "
          f"-> {dense_bf16_bytes / sp:.2f}x less decode traffic")
    # top-k agreement dense vs pruned (quality proxy)
    k = 10
    top_d = np.argsort(-np.asarray(y_dense))[:k]
    top_s = np.argsort(-np.asarray(y_sparse))[:k]
    print(f"top-{k} overlap dense vs pruned: "
          f"{len(set(top_d.tolist()) & set(top_s.tolist()))}/{k}")


if __name__ == "__main__":
    main()
