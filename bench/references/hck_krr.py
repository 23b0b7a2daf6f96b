"""Plain reference of HCK kernel ridge regression (arXiv:1608.00860).

Written from the paper and the documented key usage of the program, in
plain ``jax.numpy``, importing nothing of the program:

* padding: ``n`` is padded to ``leaf * 2**levels`` with copies of uniformly
  drawn points plus 1e-4 Gaussian jitter, targets copied;
* partition: at level ``l`` every node draws one random unit direction,
  projects its points and splits them at the median (``t > thr`` goes
  right);
* landmarks: ``rank`` points per node, uniform without replacement;
* the kernel: Gaussian ``exp(-|x - y|^2 / (2 sigma^2))``; every self block
  ``K(Z, Z)`` gets ``jitter * |Z|`` on its diagonal;
* the HCK matrix (Eq. 13-16): leaf blocks ``K(X_i, X_i)``; between the two
  children ``a``, ``b`` of node ``p`` the block ``V_a Sigma_p V_b^T`` with
  ``Sigma_p = K(Z_p, Z_p)``, ``V_leaf = K(X_leaf, Z_p) Sigma_p^-1`` and,
  for an inner node ``c``, ``V_c = [V_cl; V_cr] K(Z_c, Z_p) Sigma_p^-1``;
* the fit: ``alpha = (K_hck + lam I)^-1 y``, solved here by a Woodbury
  recursion over the tree (not the program's Algorithm 2), refined until
  the residual stops falling, with the residual computed in float64 on
  the host (:func:`solve_converged`);
* prediction (Algorithm 3): a query routed to leaf ``i`` is a member of
  ``i``: ``K(q, X_i) alpha_i + K(q, Z_p) Sigma_p^-1 d_i`` with ``d_i`` the
  off-diagonal coefficients of leaf ``i`` in ``K_hck alpha``.

The partition is a discrete function of rounded projections, so the
reference does not redraw the tree: it checks the program's tree split by
split against its own projections, and a point may lie on either side
only where its projection is within the rounding bound of the median
(:func:`check_tree`).  Everything after the tree is the reference's own.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F32_EPS = 2.0 ** -24


def pad(x, y, leaf: int, levels: int, key):
    """``(x_pad, y_pad, key_build)`` as the fit pads and splits its key."""
    kpad, kbuild = jax.random.split(key)
    n, target = x.shape[0], leaf << levels
    if n < target:
        k1, k2 = jax.random.split(kpad)
        idx = jax.random.randint(k1, (target - n,), 0, n)
        noise = 1e-4 * jax.random.normal(k2, (target - n, x.shape[1]),
                                         dtype=x.dtype)
        x = jnp.concatenate([x, x[idx] + noise], axis=0)
        y = jnp.concatenate([y, y[idx]], axis=0)
    return x, y, kbuild


def _kernel(a, b, sigma):
    """Gaussian kernel blocks: (B, m, d), (B, s, d) -> (B, m, s)."""
    d2 = (jnp.sum(a * a, -1)[:, :, None] + jnp.sum(b * b, -1)[:, None, :]
          - 2.0 * jnp.einsum("bmd,bsd->bms", a, b))
    return jnp.exp(jnp.maximum(d2, 0.0) * (-0.5 / (sigma * sigma)))


def _gram(a, sigma, jitter):
    m = a.shape[1]
    return _kernel(a, a, sigma) + (jitter * m) * jnp.eye(m, dtype=a.dtype)


def _spd_inv(s):
    """Inverse of a stack of SPD matrices through their Cholesky factors."""
    eye = jnp.broadcast_to(jnp.eye(s.shape[-1], dtype=s.dtype), s.shape)
    return jax.vmap(lambda c, e: jax.scipy.linalg.cho_solve((c, True), e))(
        jnp.linalg.cholesky(s), eye)


def _directions(key, levels: int, d: int, dtype):
    """Per-level (2**l, d) unit directions from the partition key."""
    dirs = []
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        v = jax.vmap(lambda k: jax.random.normal(k, (d,), dtype))(
            jax.random.split(sub, 1 << lvl))
        dirs.append(v / (jnp.linalg.norm(v, axis=-1, keepdims=True) + 1e-12))
    return dirs


@functools.partial(jax.jit, static_argnames=("levels",))
def check_tree(x_pad, perm, x_sorted, key_build, *, levels: int):
    """Check the program's tree; returns ``(bad, directions, thresholds)``.

    ``bad`` counts the points on the wrong side of some split by more
    than the rounding bound ``2 d eps sum_j |x_j v_j|`` of two float32
    projections, plus the rows of ``x_sorted`` that are not
    ``x_pad[perm]``, plus ``n`` if ``perm`` is not a permutation.  The
    thresholds are the reference's own medians on the checked tree.
    """
    n, d = x_pad.shape
    kpart, _ = jax.random.split(key_build)
    dirs = _directions(kpart, levels, d, x_pad.dtype)
    bad = jnp.sum(jnp.any(x_sorted != x_pad[perm], axis=1))
    hits = jnp.zeros((n,), jnp.int32).at[perm].add(1)
    bad = bad + jnp.where(jnp.all(hits == 1), 0, n)
    xs = x_pad[perm]
    thrs = []
    for lvl, v in enumerate(dirs):
        blocks = xs.reshape(1 << lvl, n >> lvl, d)
        proj = jnp.einsum("bmd,bd->bm", blocks, v)
        band = 2.0 * d * F32_EPS * jnp.einsum("bmd,bd->bm", jnp.abs(blocks),
                                              jnp.abs(v))
        srt = jnp.sort(proj, axis=1)
        m = n >> lvl
        thr = 0.5 * (srt[:, m // 2 - 1] + srt[:, m // 2])
        left = jnp.arange(m)[None, :] < m // 2
        off = jnp.where(left, proj - thr[:, None], thr[:, None] - proj)
        bad = bad + jnp.sum(off > band)
        thrs.append(thr)
    return bad, dirs, thrs


@functools.partial(jax.jit, static_argnames=("levels", "rank"))
def build(x_sorted, key_build, sigma, jitter, *, levels: int, rank: int):
    """The HCK factors on the tree order ``x_sorted``.

    Returns a dict: ``landmarks``/``sigma``/``sigma_inv`` per level
    (2**l, r, .), ``w`` per level 1..L-1 (the transfer
    ``K(Z_c, Z_p) Sigma_p^-1``), ``u`` (2**L, n0, r) and ``adiag``
    (2**L, n0, n0).
    """
    n, d = x_sorted.shape
    _, key = jax.random.split(key_build)
    lms, sig, sinv = [], [], []
    for lvl in range(levels):
        key, sub = jax.random.split(key)
        m = n >> lvl
        idx = jax.vmap(lambda k: jax.random.permutation(k, m)[:rank])(
            jax.random.split(sub, 1 << lvl))
        blocks = x_sorted.reshape(1 << lvl, m, d)
        lm = jnp.take_along_axis(blocks, idx[:, :, None], axis=1)
        s = _gram(lm, sigma, jitter)
        lms.append(lm)
        sig.append(s)
        sinv.append(_spd_inv(s))
    n_leaves = 1 << levels
    leaves = x_sorted.reshape(n_leaves, n // n_leaves, d)
    rep = lambda a: jnp.repeat(a, 2, axis=0)
    u = jnp.einsum("bms,bst->bmt",
                   _kernel(leaves, rep(lms[-1]), sigma), rep(sinv[-1]))
    w = [jnp.einsum("brs,bst->brt",
                    _kernel(lms[lvl], rep(lms[lvl - 1]), sigma),
                    rep(sinv[lvl - 1]))
         for lvl in range(1, levels)]
    return dict(landmarks=lms, sigma=sig, sigma_inv=sinv, w=w, u=u,
                adiag=_gram(leaves, sigma, jitter))


def _pairs(a):
    return a.reshape(a.shape[0] // 2, 2, *a.shape[1:])


@jax.jit
def matvec(f, b):
    """``K_hck b`` for ``b`` (n, k) in tree order, and the leaf
    off-diagonal coefficients ``d`` (2**L, r, k)."""
    adiag, u = f["adiag"], f["u"]
    n_leaves, n0, _ = u.shape
    levels = len(f["sigma"])
    bl = b.reshape(n_leaves, n0, -1)
    c = {levels: jnp.einsum("bmr,bmk->brk", u, bl)}
    for lvl in range(levels - 1, 0, -1):
        c[lvl] = jnp.einsum("brs,brk->bsk", f["w"][lvl - 1],
                            _pairs(c[lvl + 1]).sum(axis=1))
    d = None
    for lvl in range(1, levels + 1):
        sib = _pairs(c[lvl])[:, ::-1].reshape(c[lvl].shape)
        here = jnp.einsum("brs,bsk->brk",
                          jnp.repeat(f["sigma"][lvl - 1], 2, axis=0), sib)
        if d is not None:
            here = here + jnp.repeat(
                jnp.einsum("brs,bsk->brk", f["w"][lvl - 2], d), 2, axis=0)
        d = here
    y = jnp.einsum("bmn,bnk->bmk", adiag, bl) + jnp.einsum(
        "bmr,brk->bmk", u, d)
    return y.reshape(b.shape), d


@jax.jit
def factorize(f, lam):
    """RHS-independent part of the Woodbury recursion for ``K_hck + lam I``.

    Leaves: ``A_i = adiag_i + lam I``, ``Y_i = A_i^-1 U_i``,
    ``G_i = U_i^T Y_i``.  Node ``p`` with children ``a``, ``b``:
    ``S_p = [[G_a, Sigma_p^-1], [Sigma_p^-1, G_b]]``,
    ``T_p = (I - S_p^-1 diag(G_a, G_b)) [W_p; W_p]`` and
    ``G_p = W_p^T (G_a T_p,a + G_b T_p,b)``.
    """
    adiag, u = f["adiag"], f["u"]
    n0, r = u.shape[1], u.shape[2]
    levels = len(f["sigma"])
    a = adiag + lam * jnp.eye(n0, dtype=adiag.dtype)
    chol = jnp.linalg.cholesky(a)
    y_leaf = jax.vmap(lambda c, m: jax.scipy.linalg.cho_solve((c, True), m))(
        chol, u)
    g = jnp.einsum("bmr,bms->brs", u, y_leaf)
    s_inv, t = [None] * levels, [None] * levels
    for lvl in range(levels - 1, -1, -1):
        gp = _pairs(g)
        ga, gb = gp[:, 0], gp[:, 1]
        si = f["sigma_inv"][lvl]
        s = jnp.concatenate([jnp.concatenate([ga, si], 2),
                             jnp.concatenate([si, gb], 2)], 1)
        s_inv[lvl] = jnp.linalg.inv(s)
        if lvl == 0:
            break
        wp = f["w"][lvl - 1]
        g2 = jnp.concatenate([jnp.concatenate([ga, jnp.zeros_like(ga)], 2),
                              jnp.concatenate([jnp.zeros_like(gb), gb], 2)],
                             1)
        ww = jnp.concatenate([wp, wp], 1)
        t[lvl] = ww - jnp.einsum("bij,bjk,bkl->bil", s_inv[lvl], g2, ww)
        g = jnp.einsum("bsr,bst->brt", wp,
                       jnp.einsum("brs,bst->brt", ga, t[lvl][:, :r])
                       + jnp.einsum("brs,bst->brt", gb, t[lvl][:, r:]))
    return dict(chol=chol, y_leaf=y_leaf, s_inv=s_inv, t=t[1:])


@jax.jit
def _inverse_apply(f, fac, b):
    """``(K_hck + lam I)^-1 b`` from :func:`factorize`'s output."""
    u = f["u"]
    n_leaves, n0, r = u.shape
    levels = len(f["sigma"])
    bl = b.reshape(n_leaves, n0, -1)
    z = jax.vmap(lambda c, m: jax.scipy.linalg.cho_solve((c, True), m))(
        fac["chol"], bl)
    # upward: the leaf G's are rebuilt to keep the factorization small
    gl = jnp.einsum("bmr,bms->brs", u, fac["y_leaf"])
    gvec = jnp.einsum("bmr,bmk->brk", u, z)
    gmat = gl
    s = [None] * levels
    for lvl in range(levels - 1, -1, -1):
        gv = _pairs(gvec)
        gm = _pairs(gmat)
        rhs = jnp.concatenate([gv[:, 0], gv[:, 1]], 1)
        s[lvl] = jnp.einsum("bij,bjk->bik", fac["s_inv"][lvl], rhs)
        if lvl == 0:
            break
        tl = fac["t"][lvl - 1]
        wp = f["w"][lvl - 1]
        corr = (jnp.einsum("brs,bsk->brk", gm[:, 0], s[lvl][:, :r])
                + jnp.einsum("brs,bsk->brk", gm[:, 1], s[lvl][:, r:]))
        gvec = jnp.einsum("bsr,bsk->brk", wp, gv[:, 0] + gv[:, 1] - corr)
        gmat = jnp.einsum("bsr,bst->brt", wp,
                          jnp.einsum("brs,bst->brt", gm[:, 0], tl[:, :r])
                          + jnp.einsum("brs,bst->brt", gm[:, 1], tl[:, r:]))
    # downward: e_child = s_p,child + T_p,child e_p
    e = None
    for lvl in range(levels):
        here = s[lvl]                                   # (2**lvl, 2r, k)
        if e is not None:
            here = here + jnp.einsum("bij,bjk->bik", fac["t"][lvl - 1], e)
        e = here.reshape(here.shape[0] * 2, r, -1)
    out = z - jnp.einsum("bmr,brk->bmk", fac["y_leaf"], e)
    return out.reshape(b.shape)


def host64(f) -> dict:
    """The operator's factors as float64 host arrays."""
    return {k: ([np.asarray(a, np.float64) for a in f[k]]
                if isinstance(f[k], list) else np.asarray(f[k], np.float64))
            for k in ("adiag", "u", "sigma", "w")}


def matvec_host(f, b):
    """:func:`matvec` in float64 on the host, ``f`` from :func:`host64`."""
    adiag, u = f["adiag"], f["u"]
    n_leaves, n0, r = u.shape
    levels = len(f["sigma"])
    bl = b.reshape(n_leaves, n0, -1)
    k = bl.shape[2]
    c = {levels: u.transpose(0, 2, 1) @ bl}
    for lvl in range(levels - 1, 0, -1):
        c[lvl] = (f["w"][lvl - 1].transpose(0, 2, 1)
                  @ c[lvl + 1].reshape(-1, 2, r, k).sum(axis=1))
    d = None
    for lvl in range(1, levels + 1):
        sib = c[lvl].reshape(-1, 2, r, k)[:, ::-1].reshape(c[lvl].shape)
        here = np.repeat(f["sigma"][lvl - 1], 2, axis=0) @ sib
        if d is not None:
            here = here + np.repeat(f["w"][lvl - 2] @ d, 2, axis=0)
        d = here
    return (adiag @ bl + u @ d).reshape(b.shape), d


def residual(f64, x, b, lam) -> float:
    """``|(K_hck + lam I) x - b| / |b|`` in float64 on the host."""
    x = np.asarray(x, np.float64)
    b = np.asarray(b, np.float64)
    r = matvec_host(f64, x)[0] + lam * x - b
    return float(np.linalg.norm(r) / np.linalg.norm(b))


def solve_converged(f, fac, b, lam, max_steps: int = 12):
    """``alpha = (K_hck + lam I)^-1 b`` by iterative refinement: each step
    applies the float32 inverse to the residual, which is computed in
    float64 on the host, until the residual stops falling.  Returns
    ``(alpha, d, residuals)``: alpha (n, k) and the leaf coefficients
    ``d`` of :func:`matvec` as float32 device arrays, and the relative
    residual before each step."""
    f64 = host64(f)
    b64 = np.asarray(b, np.float64)
    nb = np.linalg.norm(b64)
    x = np.asarray(_inverse_apply(f, fac, b), np.float64)
    best, history = None, []
    for _ in range(max_steps + 1):
        y, d = matvec_host(f64, x)
        r = b64 - (y + lam * x)
        history.append(float(np.linalg.norm(r) / nb))
        if best is not None and history[-1] >= best[0]:
            break
        best = (history[-1], x, d)
        x = x + np.asarray(_inverse_apply(f, fac, jnp.asarray(r, b.dtype)),
                           np.float64)
    _, x, d = best
    return (jnp.asarray(x, b.dtype), jnp.asarray(d, b.dtype), history)


@functools.partial(jax.jit, static_argnames=("levels",))
def route(dirs, thrs, q, *, levels: int):
    """Leaf of every query, and whether some split on its path is closer
    to it than the rounding bound of two float32 projections."""
    node = jnp.zeros((q.shape[0],), jnp.int32)
    near = jnp.zeros((q.shape[0],), bool)
    d = q.shape[1]
    for lvl in range(levels):
        v = dirs[lvl][node]
        t = jnp.sum(q * v, axis=1)
        band = 2.0 * d * F32_EPS * jnp.sum(jnp.abs(q) * jnp.abs(v), axis=1)
        thr = thrs[lvl][node]
        near = near | (jnp.abs(t - thr) <= band)
        node = 2 * node + (t > thr).astype(jnp.int32)
    return node, near


@jax.jit
def predict(f, x_sorted, alpha, d_leaf, leaf, q, sigma):
    """Algorithm 3 for queries ``q`` routed to ``leaf``: (q, k)."""
    n_leaves, n0, _ = f["u"].shape
    xl = x_sorted.reshape(n_leaves, n0, -1)[leaf]
    al = alpha.reshape(n_leaves, n0, -1)[leaf]
    parent = leaf // 2
    lm = f["landmarks"][-1][parent]
    kq = _kernel(q[:, None, :], xl, sigma)[:, 0]                 # (q, n0)
    kz = _kernel(q[:, None, :], lm, sigma)[:, 0]                 # (q, r)
    uq = jnp.einsum("qr,qrs->qs", kz, f["sigma_inv"][-1][parent])
    return (jnp.einsum("qm,qmk->qk", kq, al)
            + jnp.einsum("qr,qrk->qk", uq, d_leaf[leaf]))


def fit(x_sorted, key_build, y_sorted, *, sigma, jitter, lam, levels, rank):
    """Factors, alpha (n, k), leaf coefficients and the residual history
    (:func:`solve_converged`) of the reference fit."""
    f = build(x_sorted, key_build, sigma, jitter, levels=levels, rank=rank)
    fac = factorize(f, lam)
    alpha, d_leaf, history = solve_converged(f, fac, y_sorted, lam)
    del fac
    return f, alpha, d_leaf, history

