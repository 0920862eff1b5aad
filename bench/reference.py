"""Plain reference of the uHD datapath, written from its definition.

It imports nothing of the program and takes nothing the program made:
the Sobol thresholds are derived here from the configuration and the
seed, the class sums are trained here, and top-k is found by brute
force.  What it shares with the program is only the benchmark's own
input data (images, labels, store rows), made from the run's seed.

Definition (uHD, arXiv:2311.10778, as this configuration states it):

  * Feature h uses Sobol dimension h: dimension 0 is van der Corput;
    dimension h >= 1 uses the h-th primitive polynomial over GF(2)
    (by increasing degree, then value) with odd initial direction
    numbers m_k < 2^k drawn from ``SeedSequence([seed, h])``.  Point p
    of a dimension is the XOR of the direction integers picked by the
    bits of gray(p); thresholds are points ``skip .. skip + D - 1``
    keeping their top log2(levels) bits.
  * An image's intensities quantize to ``floor(x / max * levels)``; its
    hypervector is ``hv[d] = sum_h (2 [x_q[h] >= S[h, d]] - 1)``.
  * Class sums add the hypervectors of each class (int32).
  * Packed words hold sign bits of ``v - mean_D(v)`` (float32), bit j
    of word w for dimension 32 w + j, pad bits zero.
  * Distance is Hamming over packed words; top-k ascends by (distance,
    row index).  A label is the nearest class (lowest index on ties).

``bits`` (default log2(levels)) sets the precision of intensities and
thresholds: the control computes everything with one bit fewer.
"""

from __future__ import annotations

import functools
import math

import numpy as np

N_BITS = 32


# ---------------------------------------------------------------------------
# Sobol thresholds (host, numpy)
# ---------------------------------------------------------------------------


def _mulmod(a: int, b: int, mod: int, deg: int) -> int:
    out = 0
    while b:
        if b & 1:
            out ^= a
        b >>= 1
        a <<= 1
        if (a >> deg) & 1:
            a ^= mod
    return out


def _order_is_full(poly: int, deg: int) -> bool:
    """x has multiplicative order 2^deg - 1 modulo `poly`."""
    n = (1 << deg) - 1

    def power(e: int) -> int:
        out, base = 1, 2
        while e:
            if e & 1:
                out = _mulmod(out, base, poly, deg)
            base = _mulmod(base, base, poly, deg)
            e >>= 1
        return out

    if power(n) != 1:
        return False
    factors, m, p = set(), n, 2
    while p * p <= m:
        while m % p == 0:
            factors.add(p)
            m //= p
        p += 1
    if m > 1:
        factors.add(m)
    return all(power(n // q) != 1 for q in factors)


@functools.lru_cache(maxsize=4)
def primitive_polynomials(count: int) -> tuple[int, ...]:
    found: list[int] = []
    deg = 1
    while len(found) < count:
        for poly in range((1 << deg) | 1, 1 << (deg + 1), 2):
            if _order_is_full(poly, deg):
                found.append(poly)
        deg += 1
    return tuple(found[:count])


def direction_integers(n_dims: int, seed: int) -> np.ndarray:
    """(n_dims, 32) direction integers v_k = m_k << (32 - k), as uint64."""
    polys = primitive_polynomials(max(n_dims - 1, 1))
    out = np.zeros((n_dims, N_BITS), np.uint64)
    for dim in range(n_dims):
        m = [0] * (N_BITS + 1)
        if dim == 0:
            m = [1] * (N_BITS + 1)
        else:
            poly = polys[dim - 1]
            s = poly.bit_length() - 1
            draw = np.random.default_rng(np.random.SeedSequence([seed, dim]))
            for k in range(1, min(s, N_BITS) + 1):
                m[k] = 2 * int(draw.integers(0, 1 << (k - 1))) + 1
            for k in range(s + 1, N_BITS + 1):
                val = m[k - s] ^ (m[k - s] << s)
                for j in range(1, s):
                    if (poly >> (s - j)) & 1:
                        val ^= m[k - j] << j
                m[k] = val
        for k in range(1, N_BITS + 1):
            out[dim, k - 1] = (m[k] << (N_BITS - k)) & 0xFFFFFFFF
    return out


def thresholds(cfg: dict, seed: int, bits: int | None = None) -> np.ndarray:
    """(H, D) int32 quantized Sobol thresholds."""
    h, d, skip = cfg["n_features"], cfg["d"], cfg["sobol_skip"]
    bits = int(math.log2(cfg["levels"])) if bits is None else bits
    v = direction_integers(h, seed)
    idx = np.arange(skip, skip + d, dtype=np.uint64)
    gray = idx ^ (idx >> np.uint64(1))
    pts = np.zeros((h, d), np.uint64)
    for b in range(int(gray.max()).bit_length()):
        on = ((gray >> np.uint64(b)) & np.uint64(1)).astype(bool)
        pts[:, on] ^= v[:, b : b + 1]
    return (pts >> np.uint64(N_BITS - bits)).astype(np.int32)


# ---------------------------------------------------------------------------
# device side (plain jax.numpy)
# ---------------------------------------------------------------------------


class Reference:
    """The configuration's datapath at a given precision."""

    def __init__(self, cfg: dict, seed: int, bits: int | None = None):
        import jax.numpy as jnp

        self.cfg = cfg
        self.bits = int(math.log2(cfg["levels"])) if bits is None else int(bits)
        self.table = jnp.asarray(thresholds(cfg, seed, self.bits))

    def quantize(self, x):
        import jax.numpy as jnp

        # intensities are integers 0..max: exact integer floor
        xi = jnp.asarray(x).astype(jnp.int32)
        return (xi * (1 << self.bits)) // int(self.cfg["max_intensity"])

    def encode(self, x):
        """(B, H) images -> (B, D) int32 hypervectors."""
        return _encode(self.quantize(x), self.table)

    def class_sums(self, x, y, block: int = 2048):
        """Class sums of (N, H) images with (N,) labels, int32 (C, D)."""
        import jax.numpy as jnp

        c = self.cfg["n_classes"]
        out = jnp.zeros((c, self.cfg["d"]), jnp.int32)
        for i in range(0, x.shape[0], block):
            out = out + _bundle(self.encode(x[i : i + block]), y[i : i + block], c)
        return out

    @staticmethod
    def pack(v):
        """Sign bits of v - mean_D(v), packed.  The mean is float32, as
        the configuration states, and taken eagerly, one operation at a
        time as `HDCModel.pack` takes it: where the sum over D passes
        2^24 (trained class sums) float32 rounds, and this keeps the
        rounding the configuration's."""
        import jax.numpy as jnp

        x = jnp.asarray(v).astype(jnp.float32)
        return _pack_bits((x - x.mean(-1, keepdims=True)) >= 0)

    @staticmethod
    def pack_exact(v):
        """The same sign bits in exact integer arithmetic:
        D * v - sum_D(v) >= 0."""
        import jax.numpy as jnp

        v = np.asarray(v, np.int64)
        bits = v.shape[-1] * v - v.sum(-1, keepdims=True) >= 0
        return _pack_bits(jnp.asarray(bits))

    def query_words(self, x):
        return _pack_queries(self.encode(x))

    def labels(self, x, class_words, block: int = 1024):
        import jax.numpy as jnp

        out = []
        for i in range(0, x.shape[0], block):
            q = self.query_words(x[i : i + block])
            dist = _distances(q, class_words)
            out.append(jnp.argmin(dist, axis=1))  # first minimum: lowest index
        return np.concatenate([np.asarray(o, np.int32) for o in out])

    def topk(self, x, rows, k: int, block: int = 1 << 17):
        """(B, k) row indices and distances of the k nearest rows, by
        (distance, index), for (B, H) query images."""
        return topk_words(self.query_words(x), rows, k, block)


def topk_words(q, rows, k: int, block: int = 1 << 17):
    """(B, k) indices and distances of the k nearest of `rows` for packed
    queries `q`: exact per block of rows on the device (key = distance,
    then index), merged on the host."""
    n = rows.shape[0]
    shift = max(1, (block - 1).bit_length())
    cand_d, cand_i = [], []
    for start in range(0, n, block):
        dist = _distances(q, rows[start : start + block])
        d_i, i_i = _block_topk(dist, k, shift)
        cand_d.append(np.asarray(d_i))
        cand_i.append(np.asarray(i_i) + start)
    dist = np.concatenate(cand_d, axis=1).astype(np.int64)
    idx = np.concatenate(cand_i, axis=1).astype(np.int64)
    order = np.lexsort((idx, dist), axis=1)[:, :k]
    return (np.take_along_axis(idx, order, 1).astype(np.int32),
            np.take_along_axis(dist, order, 1).astype(np.int32))


@functools.cache
def _compiled():
    import jax
    import jax.numpy as jnp

    @jax.jit
    def encode(xq, table):
        def body(h, acc):
            return acc + (xq[:, h][:, None] >= table[h][None, :]).astype(jnp.int32)

        count = jax.lax.fori_loop(
            0, table.shape[0], body,
            jnp.zeros((xq.shape[0], table.shape[1]), jnp.int32),
        )
        return 2 * count - table.shape[0]

    @functools.partial(jax.jit, static_argnums=2)
    def bundle(hv, y, c):
        return jax.ops.segment_sum(hv, y, num_segments=c)

    @jax.jit
    def pack_bits(bits):
        d = bits.shape[-1]
        pad = (-d) % 32
        bits = jnp.pad(bits, ((0, 0), (0, pad)))
        bits = bits.reshape(bits.shape[0], -1, 32).astype(jnp.uint32)
        return (bits << jnp.arange(32, dtype=jnp.uint32)).sum(-1, dtype=jnp.uint32)

    @jax.jit
    def distances(q, rows):
        def one(qi):
            return jax.lax.population_count(rows ^ qi[None, :]).astype(jnp.int32).sum(-1)

        return jax.lax.map(one, q)

    @functools.partial(jax.jit, static_argnums=(1, 2))
    def block_topk(dist, k, shift):
        key = (dist << shift) | jnp.arange(dist.shape[1], dtype=jnp.int32)[None, :]
        neg, _ = jax.lax.top_k(-key, k)
        key = -neg
        return key >> shift, key & ((1 << shift) - 1)

    @jax.jit
    def pack_queries(hv):
        # hypervector sums over D stay below 2^24: the float32 mean is exact
        x = hv.astype(jnp.float32)
        return pack_bits((x - x.mean(-1, keepdims=True)) >= 0)

    return encode, bundle, pack_bits, distances, block_topk, pack_queries


def _encode(xq, table):
    return _compiled()[0](xq, table)


def _bundle(hv, y, c):
    return _compiled()[1](hv, y, c)


def _pack_bits(bits):
    return _compiled()[2](bits)


def _pack_queries(hv):
    return _compiled()[5](hv)


def _distances(q, rows):
    return _compiled()[3](q, rows)


def _block_topk(dist, k, shift):
    return _compiled()[4](dist, k, shift)
