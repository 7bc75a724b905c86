"""The float64 reference the in-place decode reads of every family are
held to (tests/test_serve.py, test_serve_latent.py)."""
import numpy as np


def token_beside_pages_oracle(q_parts, k_parts, v, slots, lens, kn_parts,
                              vn, scale):
    """Float64 reference of the in-place decode read, lane by lane: the
    lane's query (a sum of score parts) over positions ``0 .. len - 1``
    of its row and over its own token, which is not in the pages.  Pages ``[rows, kv_heads, L, d]``, queries ``[S, heads, d]``,
    token ``[S, kv_heads, d]``."""
    f64 = lambda a: np.asarray(a, np.float64)
    q_parts, k_parts, kn_parts = (list(map(f64, x))
                                  for x in (q_parts, k_parts, kn_parts))
    v, vn = f64(v), f64(vn)
    S, H = q_parts[0].shape[:2]
    Hkv = v.shape[1]
    out = np.zeros((S, H, v.shape[-1]))
    for i in range(S):
        n = int(lens[i])
        at = list(range(n))
        for h in range(H):
            kh = h // (H // Hkv)
            s = sum(np.concatenate([k[slots[i], kh, at] @ q[i, h],
                                    kn[i, kh][None] @ q[i, h]])
                    for q, k, kn in zip(q_parts, k_parts, kn_parts)) * scale
            p = np.exp(s - s.max())
            out[i, h] = (p / p.sum()) @ np.concatenate(
                [v[slots[i], kh, at], vn[i, kh][None]])
    return out
