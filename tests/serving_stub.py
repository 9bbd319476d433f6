"""Tiny deterministic attention LM for serving-mechanics tests.

Just enough model for ``BatchedServer``: greedy-decodable, jittable, and
— crucially — its prefill routes causal self-attention through the
``"attention"`` ops-registry site exactly like the real models'
``attention_chunked``, so a hot-swapped variant genuinely changes the
prefill computation.  Decode is a cheap masked attention over the cache
(kept off the site, mirroring the real decode path).

The stub speaks the full continuous-batching model contract:

* ``prefill(..., lengths=[B])`` — packed right-padded batches: logits are
  gathered at each row's true last token (causal masking keeps the pad
  tail from leaking backwards).
* ``decode_step(..., pos)`` with ``pos`` scalar *or* a per-slot [B]
  vector (ragged decode): cache writes and the attention mask are
  per-row.

Cache leaves are ``[layer=1, batch, max_len, DIM]`` to match the
``[:, s:s+1]`` slot-splice layout ``BatchedServer`` expects.
"""
import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops

VOCAB, DIM = 32, 8


class _Cfg:
    family = "dense"
    vocab_size = VOCAB


def _naive_causal(x):
    B, S, D = x.shape
    s = jnp.einsum("bsd,btd->bst", x, x) / np.sqrt(D)
    mask = jnp.tril(jnp.ones((S, S), bool))
    s = jnp.where(mask[None], s, -1e30)
    return jnp.einsum("bst,btd->bsd", jax.nn.softmax(s, axis=-1), x)


class StubModel:
    cfg = _Cfg()

    def init_params(self, key):
        return {"emb": jax.random.normal(key, (VOCAB, DIM)) * 0.5}

    def init_cache(self, batch, max_len):
        z = jnp.zeros((1, batch, max_len, DIM))
        return {"k": z, "v": z}

    def prefill(self, params, tokens, max_len=None, lengths=None):
        x = params["emb"][tokens]                       # [B,S,D]
        impl = ops.get_impl("attention")
        if impl is None:
            out = _naive_causal(x)
        else:
            q = x[:, :, None, :]                        # [B,S,H=1,hd]
            out = impl(q, q, q, causal=True, softcap=0.0)[:, :, 0, :]
        logits = out @ params["emb"].T                  # [B,S,V]
        B, S, _ = x.shape
        if lengths is not None:                         # packed ragged rows
            idx = jnp.clip(lengths.astype(jnp.int32) - 1, 0, S - 1)
            logits = jnp.take_along_axis(logits, idx[:, None, None], axis=1)
        max_len = max_len or S
        k = jnp.zeros((1, B, max_len, DIM)).at[:, :, :S].set(x[None])
        return logits, {"k": k, "v": k}

    def decode_step(self, params, cache, token, pos):
        x = params["emb"][token[:, 0]]                  # [B,D]
        B = x.shape[0]
        pos = jnp.asarray(pos, jnp.int32)
        posv = jnp.broadcast_to(pos.reshape(-1), (B,))  # scalar or [B]
        rows = jnp.arange(B)
        k = cache["k"].at[0, rows, posv].set(x)
        v = cache["v"].at[0, rows, posv].set(x)
        kpos = jnp.arange(k.shape[2])
        s = jnp.einsum("bd,btd->bt", x, k[0]) / np.sqrt(DIM)
        s = jnp.where(kpos[None, :] <= posv[:, None], s, -1e30)
        out = jnp.einsum("bt,btd->bd", jax.nn.softmax(s, axis=-1), v[0])
        logits = (out @ params["emb"].T)[:, None]       # [B,1,V]
        return logits, {"k": k, "v": v}


def make_server(**kw):
    from repro.serve import BatchedServer
    model = StubModel()
    params = model.init_params(jax.random.PRNGKey(0))
    return BatchedServer(model, params, **kw)


def stub_generate(prompt, max_new, eos_id=None):
    """Fixed-batch greedy reference for a single prompt, via generate()."""
    from repro.serve import generate
    model = StubModel()
    params = model.init_params(jax.random.PRNGKey(0))
    row = generate(model, params, jnp.asarray(np.asarray(prompt)[None, :]),
                   max_new=max_new, eos_id=eos_id)[0]
    toks = [int(t) for t in row]
    if eos_id is not None and eos_id in toks:
        toks = toks[:toks.index(eos_id) + 1]   # server stops at first EOS
    return toks


def prompts(n, length=8, seed=0):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, VOCAB, length).astype(np.int32)
            for _ in range(n)]
