"""Plain reference of DeKRR-DDRF (arXiv:2405.07791, Alg. 1), for the check.

It imports nothing of the program and takes nothing the program made: it
draws its own DDRF candidates from the fit's key, scores them, builds the
Eq. 17 blocks from its own features and runs the Eq. 19 rounds. The only
things it takes from a run are the answers it judges: which candidates
the program selected, the θ it returned and the rounds it ran.

Notation (paper): Z_{i,j} = node i's features on node j's data, [D_i, N_j];
c̃_{j,·} = c_{j,·} / (N (|N_j| + 1)); c_self = 5 c_nei.

    A_j = (1/N + 2c̃_{j,self} + |N_j| c̃_{j,nei}) Z_jj Z_jjᵀ + (λ/J) I
          + Σ_{p∈N_j} c̃_{p,nei} Z_{j,p} Z_{j,p}ᵀ,          G_j = A_j⁻¹
    d_j = Z_jj y_j / N,     S_j = 2 c̃_{j,self} Z_jj Z_jjᵀ
    P_{j,p} = c̃_{j,nei} Z_jj Z_{p,j}ᵀ + c̃_{p,nei} Z_{j,p} Z_{p,p}ᵀ
    θ_j ← G_j (d_j + S_j θ_j + Σ_{p∈N_j} P_{j,p} θ_p)           (Eq. 19)

Features: z(x) = √(2/D) cos(ωᵀx + b), ω ~ N(0, σ⁻² I), b ~ U[0, 2π).
DDRF (energy score, D0 = 20·D candidates): S(ω) = (Σ_n y_n cos(ωᵀx_n + b))²/N²,
keep the D largest.

`precision` is one of:
  "float64"        the reference (run it on the CPU under x64);
  "float32"        float32 with every matmul at full float32 precision;
  "float32_3pass"  the control: float32, every matmul as three bf16 passes
                   (hi·hi + hi·lo + lo·hi), which is what a TPU's
                   `Precision.HIGH` computes, done the same on any device.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

PRECISIONS = ("float64", "float32", "float32_3pass")
_HIGHEST = jax.lax.Precision.HIGHEST


def _dtype(precision: str):
    if precision not in PRECISIONS:
        raise ValueError(f"unknown precision {precision!r}")
    return jnp.float64 if precision == "float64" else jnp.float32


def matmul(a, b, precision: str):
    """a @ b in the given precision."""
    if precision != "float32_3pass":
        return jnp.matmul(a, b, precision=_HIGHEST)

    def split(v):
        hi = v.astype(jnp.bfloat16).astype(jnp.float32)
        return hi, (v - hi).astype(jnp.bfloat16).astype(jnp.float32)

    a_hi, a_lo = split(a)
    b_hi, b_lo = split(b)
    mm = partial(jnp.matmul, precision=_HIGHEST)   # exact bf16 products
    return mm(a_hi, b_hi) + mm(a_hi, b_lo) + mm(a_lo, b_hi)


def draw_candidates(key, dim: int, num: int, sigma: float):
    """The DDRF candidate draw for one node's key, as float32 numpy:
    (ω [num, dim], b [num]). The key is split once for the candidates
    (the second half is the resampling key), then once more into the ω
    and b streams."""
    k_cand = jax.random.split(key)[0]
    k_w, k_b = jax.random.split(k_cand)
    omega = jax.random.normal(k_w, (num, dim), jnp.float32) / sigma
    bias = jax.random.uniform(k_b, (num,), jnp.float32, maxval=2 * jnp.pi)
    return np.asarray(omega, np.float32), np.asarray(bias, np.float32)


@partial(jax.jit, static_argnames=("precision",))
def energy_scores(omega, bias, x, y, *, precision: str):
    """S(ω_k) for every candidate k: [num]."""
    proj = matmul(omega, x, precision) + bias[:, None]
    align = matmul(jnp.cos(proj), y[:, None], precision)[:, 0]
    n = x.shape[1]
    return align * align / (n * n)


@partial(jax.jit, static_argnames=("precision",))
def features(omega, bias, x, *, precision: str):
    """Z = √(2/D) cos(ωᵀx + b): [D, N]."""
    proj = matmul(omega, x, precision) + bias[:, None]
    return jnp.cos(proj) * jnp.sqrt(jnp.asarray(2.0 / omega.shape[0],
                                                proj.dtype))


def eq17_blocks(omegas, biases, xs, ys, adjacency: np.ndarray, lam: float,
                c_nei: float, c_self_ratio: float, precision: str):
    """Eq. 17 per node: (G list, d list, S list, P list of {p: P_jp})."""
    dt = _dtype(precision)
    cast = lambda a: jnp.asarray(a, dt)
    om = [cast(o) for o in omegas]
    bi = [cast(b) for b in biases]
    xs = [cast(x) for x in xs]
    ys = [cast(y) for y in ys]
    j_nodes = len(om)
    n_total = sum(int(x.shape[1]) for x in xs)
    nbrs = [list(np.nonzero(adjacency[j])[0]) for j in range(j_nodes)]
    deg = [len(n) for n in nbrs]
    ct_nei = [c_nei / (n_total * (deg[j] + 1)) for j in range(j_nodes)]
    ct_self = [c_self_ratio * c for c in ct_nei]
    mm = lambda a, b: matmul(a, b, precision)

    z = {}                                   # (i, j) -> Z_{i,j}

    def zz(i, j):
        if (i, j) not in z:
            z[(i, j)] = features(om[i], bi[i], xs[j], precision=precision)
        return z[(i, j)]

    g_list, d_list, s_list, p_list = [], [], [], []
    for j in range(j_nodes):
        z_jj = zz(j, j)
        gram = mm(z_jj, z_jj.T)
        a = (1.0 / n_total + 2.0 * ct_self[j] + deg[j] * ct_nei[j]) * gram
        a = a + (lam / j_nodes) * jnp.eye(gram.shape[0], dtype=dt)
        for p in nbrs[j]:
            z_jp = zz(j, p)
            a = a + ct_nei[p] * mm(z_jp, z_jp.T)
        g_list.append(jnp.linalg.inv(a))
        d_list.append(mm(z_jj, ys[j][:, None])[:, 0] / n_total)
        s_list.append(2.0 * ct_self[j] * gram)
        p_list.append({
            p: ct_nei[j] * mm(z_jj, zz(p, j).T)
            + ct_nei[p] * mm(zz(j, p), zz(p, p).T)
            for p in nbrs[j]})
    return g_list, d_list, s_list, p_list


@partial(jax.jit, static_argnames=("nbrs", "precision"))
def _eq19_round(g, d, s, p, theta, *, nbrs, precision: str):
    mv = lambda a, v: matmul(a, v[:, None], precision)[:, 0]
    new = []
    for j in range(len(d)):
        rhs = d[j] + mv(s[j], theta[j])
        for k, q in enumerate(nbrs[j]):
            rhs = rhs + mv(p[j][k], theta[q])
        new.append(mv(g[j], rhs))
    delta = jnp.max(jnp.stack([jnp.max(jnp.abs(a - b))
                               for a, b in zip(new, theta)]))
    return new, delta


def eq19_rounds(blocks, rounds: int | None, tol: float = 0.0,
                precision: str = "float64", budget: int = 100_000):
    """Run Eq. 19 from θ = 0 for `rounds` rounds, and on until
    max|Δθ| < tol if that stop has not come yet.

    Returns (θ after `rounds` rounds — after the stop, or after `budget`
    rounds without one, when `rounds` is None — as float64 numpy [D_j]
    per node; the first round whose max|Δθ| fell below tol, None when
    tol == 0 or no stop came within `budget` rounds; θ at that stop,
    None without one)."""
    g, d, s, p = blocks
    dt = _dtype(precision)
    nbrs = tuple(tuple(int(q) for q in pj) for pj in p)
    g, d, s = ([jnp.asarray(a, dt) for a in v] for v in (g, d, s))
    p = [[jnp.asarray(pj[q], dt) for q in nb] for pj, nb in zip(p, nbrs)]
    theta = [jnp.zeros_like(v) for v in d]
    at_rounds = [np.zeros(v.shape) for v in d]
    stop, at_stop, r = None, None, 0
    while ((rounds is not None and r < rounds)
           or (tol > 0 and stop is None and r < budget)):
        theta, delta = _eq19_round(g, d, s, p, theta, nbrs=nbrs,
                                   precision=precision)
        r += 1
        if r == rounds:
            at_rounds = [np.asarray(t, np.float64) for t in theta]
        if tol > 0 and stop is None and float(delta) < tol:
            stop = r
            at_stop = [np.asarray(t, np.float64) for t in theta]
    if rounds is None:
        at_rounds = at_stop or [np.asarray(t, np.float64) for t in theta]
    return at_rounds, stop, at_stop


def exact_theta(blocks) -> list[np.ndarray]:
    """The Eq. 19 limit point θ* = (I − M)⁻¹ b, in float64 on the host."""
    g, d, s, p = blocks
    g = [np.asarray(a, np.float64) for a in g]
    dims = [a.shape[0] for a in g]
    off = np.concatenate([[0], np.cumsum(dims)])
    m = np.zeros((off[-1], off[-1]))
    b = np.zeros(off[-1])
    for j in range(len(g)):
        sl = slice(off[j], off[j + 1])
        b[sl] = g[j] @ np.asarray(d[j], np.float64)
        m[sl, sl] = g[j] @ np.asarray(s[j], np.float64)
        for q, pjq in p[j].items():
            m[sl, off[q]:off[q + 1]] += g[j] @ np.asarray(pjq, np.float64)
    theta = np.linalg.solve(np.eye(off[-1]) - m, b)
    return [theta[off[j]:off[j + 1]] for j in range(len(g))]


def spectral_radius(blocks) -> float:
    """ρ(M) of the Eq. 19 iteration matrix."""
    g, d, s, p = blocks
    g = [np.asarray(a, np.float64) for a in g]
    dims = [a.shape[0] for a in g]
    off = np.concatenate([[0], np.cumsum(dims)])
    m = np.zeros((off[-1], off[-1]))
    for j in range(len(g)):
        sl = slice(off[j], off[j + 1])
        m[sl, sl] = g[j] @ np.asarray(s[j], np.float64)
        for q, pjq in p[j].items():
            m[sl, off[q]:off[q + 1]] += g[j] @ np.asarray(pjq, np.float64)
    return float(np.max(np.abs(np.linalg.eigvals(m))))


def predict(omegas, biases, thetas, x, precision: str):
    """Network-average answer mean_j θ_jᵀ z_j(x) for queries x [d, Q]."""
    dt = _dtype(precision)
    outs = [matmul(jnp.asarray(t, dt)[None, :],
                   features(jnp.asarray(o, dt), jnp.asarray(b, dt),
                            jnp.asarray(x, dt), precision=precision),
                   precision)[0]
            for o, b, t in zip(omegas, biases, thetas)]
    return jnp.mean(jnp.stack(outs), axis=0)
