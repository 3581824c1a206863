"""Multi-head latent attention (DeepSeek-V2, arXiv:2405.04434 §2.1): what
the callers of a ``mla`` mixer share of its two ways through attention.

A token's cached row is ``[c | k_pe]``: ``c`` the RMSNorm'd key-value
latent (``rank`` numbers) and ``k_pe`` the rotated key every head shares
(``rope`` numbers). A head's key is ``[c W_k,h | k_pe]`` and its value
``c W_v,h``, with ``W_k,h`` ``[rank, nope]`` and ``W_v,h`` ``[rank, v]``
side by side in ``wkvb`` ``[rank, heads * (nope + v)]``. The statics
``st`` are the view's ``mla``: ``nope``, ``rope``, ``v``, ``rank``.

- **expanded** (a prompt's own rows, :func:`expand`): keys and values of
  every head, for causal attention within the prompt at a query-key head
  of ``nope + rope`` and a value head of ``v``.
- **absorbed** (rows already cached, :func:`absorb_q` and
  :func:`absorb_o`): ``q_nope,h . (c W_k,h) = (q_nope,h W_k,h^T) . c``, so
  the expansion's key half goes into the query once a step, the score is
  the product with the row as it lies in the cache, the value is the
  row's latent, and the expansion's value half is applied to the
  ``rank``-wide result. Nothing of a cached token is ever expanded.
"""
from __future__ import annotations

__all__ = ["expand", "absorb_q", "absorb_o", "LANES", "row_lanes"]

#: lanes of a tile: a cached row is padded to a whole number of them
LANES = 128


def row_lanes(st) -> int:
    """Lanes a cached row takes: ``rank + rope`` rounded up to whole
    128-lane tiles (576 -> 640 at the published sizes). On the device a
    ``[..., 576]`` array is laid out in 640 lanes whatever it is called,
    and Mosaic refuses a slice of 576 of them (``kv_write``'s chunk and
    ``mla_decode``'s page alike: tests/test_tpu_aot_compile.py), so the
    padding is written out and holds zeros."""
    return -(-(st["rank"] + st["rope"]) // LANES) * LANES


def _halves(lp, st):
    w = lp["wkvb"].reshape(st["rank"], -1, st["nope"] + st["v"])
    return w[:, :, :st["nope"]], w[:, :, st["nope"]:]


def expand(lp, st, latent):
    """(k ``[T, heads, nope + rope]``, v ``[T, heads, v]``) of the rows
    ``latent`` ``[T, rank + rope]``."""
    import jax.numpy as jnp

    t = latent.shape[0]
    c, k_pe = latent[:, :st["rank"]], latent[:, st["rank"]:]
    kv = (c @ lp["wkvb"]).reshape(t, -1, st["nope"] + st["v"])
    k_pe = jnp.broadcast_to(k_pe[:, None, :],
                            (t, kv.shape[1], st["rope"]))
    return (jnp.concatenate([kv[:, :, :st["nope"]], k_pe], axis=-1),
            kv[:, :, st["nope"]:])


def absorb_q(lp, st, q_nope, q_pe, lanes=None):
    """``[rows, heads, lanes]``: each head's query against a cached row
    as it lies (``q_nope W_k^T`` over the latent's lanes, ``q_pe`` over
    the rotated key's, zeros over the padding)."""
    import jax.numpy as jnp

    w_k, _ = _halves(lp, st)
    q_lat = jnp.einsum("rhd,chd->rhc", q_nope, w_k)
    parts = [q_lat.astype(q_nope.dtype), q_pe]
    pad = (lanes or 0) - st["rank"] - st["rope"]
    if pad > 0:
        parts.append(jnp.zeros(q_pe.shape[:2] + (pad,), q_pe.dtype))
    return jnp.concatenate(parts, axis=-1)


def absorb_o(lp, st, out):
    """``[rows, heads * v]``: the expansion's value half applied to the
    softmax-weighted latents ``out`` ``[rows, heads, rank]``."""
    import jax.numpy as jnp

    _, w_v = _halves(lp, st)
    ctx = jnp.einsum("rhc,chd->rhd", out, w_v)
    return ctx.reshape(out.shape[0], -1)
