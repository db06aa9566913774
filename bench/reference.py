"""Plain reference of the solves the benchmark times.

Written from the paper (arXiv 1512.02970, Algorithms 1 and 2) and
independent of the program: it imports nothing of ``repro`` and takes
nothing that the program made. Given the same data, step size and seed it
performs the same updates in the same order, so its iterates are what a
correct solve returns, up to float32 rounding.

* Algorithm 1 (``centralvr``, one worker): one epoch of plain SGD in the
  order ``permutation(k_init, n)`` fills the table of scalar residuals;
  then each epoch ``e`` visits ``permutation(split(k_run, epochs)[e], n)``
  with the update ``x -= eta * ((s_new - s_old) a_i + gbar + 2 lam x)``
  and refreshes ``gbar`` from the epoch's running mean.
* Algorithm 2 (``centralvr_sync``, p workers on contiguous row blocks):
  every worker runs the SGD epoch from 0 in the order
  ``permutation(split(k_init, p)[w], ns)``, then the central node
  averages x and gbar; round ``r`` gives worker ``w`` the order
  ``permutation(split(split(k_run, rounds)[r], p)[w], ns)``, and ends
  with the same average.

``k_init, k_run = split(PRNGKey(seed))``: the seed's meaning, as the
program documents it (``RunSpec.seed``). The step size is the program's
documented default, ``0.3 / L`` with ``L = curv * max_i |a_i|^2 + 2 lam``.

``precision`` is ``"highest"`` (float32, the configuration's precision)
or ``"bf16x3"``: every dot product split into bfloat16 high and low
halves and summed over three passes, as the TPU's ``high`` matmul
precision does. The second is the control of ``bench/compare.py``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CURVATURE = {"logistic": 0.25, "ridge": 2.0}
ETA_SCALE = 0.3
HIGHEST = jax.lax.Precision.HIGHEST


def _split_bf16(v):
    hi = v.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (v - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def _dot(a, x, precision: str):
    """``a @ x`` for a vector or matrix ``a`` at the given precision."""
    if precision == "highest":
        return jnp.dot(a, x, precision=HIGHEST)
    if precision == "bf16x3":
        ah, al = _split_bf16(a)
        xh, xl = _split_bf16(x)
        return (jnp.dot(ah, xh, precision=HIGHEST)
                + jnp.dot(ah, xl, precision=HIGHEST)
                + jnp.dot(al, xh, precision=HIGHEST))
    raise ValueError(f"unknown precision {precision!r}")


def _deriv(z, b, loss: str):
    """l'(z; b): the scalar residual the CentralVR table stores."""
    if loss == "logistic":
        return -b / (1.0 + jnp.exp(b * z))
    if loss == "ridge":
        return 2.0 * (z - b)
    raise ValueError(f"unknown loss {loss!r}")


def _epoch(A, b, lam, loss, eta, x, table, gbar, order, sgd: bool,
           precision: str):
    """One pass of one worker over ``order``; returns (x, table, mean)."""
    ns = A.shape[0]

    def step(carry, i):
        x, table, acc = carry
        a = A[i]
        s = _deriv(_dot(a, x, precision), b[i], loss)
        if sgd:
            g = s * a + 2.0 * lam * x
        else:
            g = (s - table[i]) * a + gbar + 2.0 * lam * x
        return (x - eta * g, table.at[i].set(s), acc + s * a / ns), None

    (x, table, acc), _ = jax.lax.scan(
        step, (x, table, jnp.zeros_like(x)), order)
    return x, table, acc


@functools.partial(jax.jit, static_argnames=("loss", "algo", "p", "rounds",
                                             "precision"))
def _iterates(A, b, lam, eta, key, loss: str, algo: str, p: int,
              rounds: int, precision: str):
    n, d = A.shape
    k_init, k_run = jax.random.split(key)
    run_keys = jax.random.split(k_run, rounds)
    ns = n // p
    Aw, bw = A.reshape(p, ns, d), b.reshape(p, ns)
    if algo == "centralvr":
        orders0 = jax.random.permutation(k_init, n)[None]
        orders = jax.vmap(lambda k: jax.random.permutation(k, n))(
            run_keys)[:, None]
    elif algo == "centralvr_sync":
        def perms(k):
            return jax.vmap(lambda kk: jax.random.permutation(kk, ns))(
                jax.random.split(k, p))
        orders0 = perms(k_init)
        orders = jax.vmap(perms)(run_keys)
    else:
        raise ValueError(f"no reference for algorithm {algo!r}")

    epoch = jax.vmap(
        lambda Ai, bi, x, table, gbar, order, sgd: _epoch(
            Ai, bi, lam, loss, eta, x, table, gbar, order, sgd, precision),
        in_axes=(0, 0, None, 0, None, 0, None))
    zeros = jnp.zeros((d,), A.dtype)
    xs, tables, accs = epoch(Aw, bw, zeros, jnp.zeros((p, ns), A.dtype),
                             zeros, orders0, True)

    def one_round(carry, order):
        x, tables, gbar = carry
        xs, tables, accs = epoch(Aw, bw, x, tables, gbar, order, False)
        x = xs.mean(0)
        return (x, tables, accs.mean(0)), x

    (_, tables, _), x_hist = jax.lax.scan(
        one_round, (xs.mean(0), tables, accs.mean(0)), orders)
    return x_hist, tables


@functools.partial(jax.jit, static_argnames=("loss", "precision"))
def _rels(A, b, lam, x_hist, loss: str, precision: str):
    """||grad f(x_r)|| / ||grad f(0)|| for each row of ``x_hist``."""
    n = A.shape[0]

    def grad_norm(x):
        s = _deriv(_dot(A, x, precision), b, loss)
        return jnp.linalg.norm(_dot(A.T, s, precision) / n + 2.0 * lam * x)

    g0 = grad_norm(jnp.zeros_like(x_hist[0]))
    return jax.lax.map(grad_norm, x_hist) / g0


def step_size(A64: np.ndarray, lam: float, loss: str) -> float:
    """The default step size of the solver, ``0.3 / L``, in float32."""
    row_sq = np.max(np.einsum("ij,ij->i", A64, A64))
    L = np.float32(CURVATURE[loss] * row_sq + 2.0 * lam)
    return float(np.float32(ETA_SCALE) / L)


def solve(A, b, lam: float, loss: str, *, algo: str, p: int, rounds: int,
          seed: int, eta: float, precision: str = "highest"):
    """The reference solve: ``(x_hist, rels, table)``, the iterate after
    each round (rounds, d), the rel-grad-norm of each, computed on the
    device at ``precision``, and the final table of scalar residuals,
    flattened in worker-major order."""
    key = jax.random.PRNGKey(int(seed))
    with jax.default_matmul_precision("highest"):
        x_hist, table = _iterates(A, b, jnp.float32(lam), jnp.float32(eta),
                                  key, loss, algo, p, rounds, precision)
        rels = _rels(A, b, jnp.float32(lam), x_hist, loss, precision)
    return np.asarray(x_hist), np.asarray(rels), np.asarray(table).ravel()


def rel_grad_norm64(A64: np.ndarray, b64: np.ndarray, lam: float,
                    loss: str, x) -> float:
    """The rel-grad-norm of ``x`` in float64 on the host: the accuracy the
    solve claims, judged without the device's rounding."""
    def grad(x):
        z = A64 @ x
        if loss == "logistic":
            s = -b64 / (1.0 + np.exp(b64 * z))
        else:
            s = 2.0 * (z - b64)
        return A64.T @ s / A64.shape[0] + 2.0 * lam * x

    x = np.asarray(x, np.float64)
    return float(np.linalg.norm(grad(x)) / np.linalg.norm(grad(0.0 * x)))
