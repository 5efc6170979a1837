"""Plain reference of the TNN column algorithm, for the check of ``correct``.

Written from the documented semantics of the fused path
(``docs/kernels.md``, ``docs/backends.md``, ``docs/serving.md``), in
straightforward ``jax.numpy``, one design and one volley at a time, with
no kernels, padding, planes or batching.  It imports nothing of the
program and takes nothing the program made: encodes, initial weights,
thresholds and the service's re-fit schedule are all derived here from
the inputs and the seed.

* Encode: latency code, ``t = round((1 - v) * (t_max - 1))`` of the
  min-max normalised series.
* Fire (training): the RNL body potential ``V(t) = sum_i min(relu(t -
  x_i), w_i)`` on the integer grid of the resident float weights
  (``round(clip(w, 0, w_max))``); the firing time is the count of
  sub-threshold cycles in ``[0, t_max)``.  Integer arithmetic, exact in
  float32.
* 1-WTA with index tie-break, then expected STDP with the 'half'
  stabiliser on the float weights.
* Assign: the same fire on the float weights themselves (the batched
  assign's documented float-weight fire); the id is the earliest-firing
  neuron, or ``q`` when none fires.

``dtype`` selects the precision of the weights and the arithmetic:
float32 as the configurations state, bfloat16 for the control.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np


# ------------------------------------------------------------ the column
def encode(x, t_max: int):
    """Latency code of series ``[..., L]`` -> int32 spike times."""
    x = jnp.asarray(x, jnp.float32)
    lo = x.min(axis=-1, keepdims=True)
    hi = x.max(axis=-1, keepdims=True)
    v = (x - lo) / (hi - lo + 1e-9)
    t = jnp.round((1.0 - v) * (t_max - 1))
    return jnp.clip(t, 0, t_max - 1).astype(jnp.int32)


def _potential(w, x, t_max: int, dtype):
    """Body potential ``[q, t_max]`` of one volley ``x`` [p] on weights
    ``w`` [p, q]; inputs at or past ``t_max`` are silent."""
    tv = jnp.arange(t_max, dtype=dtype)
    ramp = jnp.maximum(tv[None, :] - x.astype(dtype)[:, None], 0)
    ramp = jnp.where((x < t_max)[:, None], ramp, 0).astype(dtype)
    return jnp.minimum(ramp[:, None, :], w.astype(dtype)[:, :, None]).sum(axis=0)


def fire_times(w, x, threshold, t_max: int, dtype=jnp.float32):
    """Firing time of each neuron: sub-threshold cycles, capped at t_max."""
    below = _potential(w, x, t_max, dtype) < threshold
    return jnp.minimum(below.sum(axis=-1), t_max).astype(jnp.int32)


def winners(t_fire, k: int, t_max: int):
    """k-WTA with index tie-break: winners keep their time, the rest
    read ``t_max`` (silent)."""
    q = t_fire.shape[-1]
    key = t_fire * q + jnp.arange(q, dtype=jnp.int32)
    rank = jnp.argsort(jnp.argsort(key))
    return jnp.where((rank < k) & (t_fire < t_max), t_fire, t_max)


def stdp(w, x, y, mu_capture, mu_backoff, mu_search, w_max: int, t_max: int,
         stabilize: bool):
    """Expected-mode STDP of one volley on weights ``w`` in their dtype."""
    dt = w.dtype
    xs = (x < t_max)[:, None]
    ys = (y < t_max)[None, :]
    xc = x[:, None]
    yc = y[None, :]
    if stabilize:
        frac = jnp.clip(w * jnp.asarray(1.0 / w_max, dt), 0, 1)
        eps = jnp.asarray(1.0 / (2 * w_max), dt)
        s_plus = (1 - frac) + eps
        s_minus = frac + eps
    else:
        s_plus = s_minus = jnp.ones_like(w)
    capture = xs & ys & (xc <= yc)
    backoff = (xs & ys & (xc > yc)) | (~xs & ys)
    search = xs & ~ys
    delta = jnp.where(capture, jnp.asarray(mu_capture, dt) * s_plus,
                      jnp.zeros((), dt))
    delta = jnp.where(backoff, -jnp.asarray(mu_backoff, dt) * s_minus, delta)
    delta = jnp.where(search, jnp.asarray(mu_search, dt), delta)
    return jnp.clip(w + delta, 0, w_max).astype(dt)


@functools.partial(jax.jit, static_argnames=("t_max", "epochs", "statics", "dtype"))
def fit(w, xs, threshold, *, t_max: int, epochs: int, statics: tuple, dtype):
    """Online STDP over volleys ``xs`` [N, p] for ``epochs`` passes, one
    volley at a time.  ``statics`` is ``(w_max, wta_k, mu_capture,
    mu_backoff, mu_search, stabilize)``."""
    w_max, k, mu_c, mu_b, mu_s, stab = statics
    w = w.astype(dtype)

    def volley(wc, x):
        w_fire = jnp.round(jnp.clip(wc, 0, w_max))
        y = winners(fire_times(w_fire, x, threshold, t_max, dtype), k, t_max)
        return stdp(wc, x, y, mu_c, mu_b, mu_s, w_max, t_max, stab), None

    def epoch(wc, _):
        return jax.lax.scan(volley, wc, xs)[0], None

    return jax.lax.scan(epoch, w, None, length=epochs)[0]


@functools.partial(jax.jit, static_argnames=("t_max", "dtype", "block"))
def assign(w, xs, threshold, *, t_max: int, dtype, block: int = 32):
    """Cluster id of each volley of ``xs`` [N, p] on float weights ``w``:
    the earliest-firing neuron (lowest index on ties), ``q`` if none."""
    n, p = xs.shape
    q = w.shape[1]
    pad = (-n) % block
    xb = jnp.concatenate([xs, jnp.full((pad, p), t_max, xs.dtype)])
    t = jax.lax.map(
        jax.vmap(lambda x: fire_times(w, x, threshold, t_max, dtype)),
        xb.reshape(-1, block, p),
    ).reshape(-1, q)[:n]
    spiked = (t < t_max).any(axis=-1)
    return jnp.where(spiked, jnp.argmin(t, axis=-1), q).astype(jnp.int32)


def statics(config: dict) -> tuple:
    """``fit``'s statics from a configuration file's neuron, WTA and STDP
    entries."""
    s = config["stdp"]
    return (int(config["neuron"]["w_max"]), int(config["wta"]["k"]),
            float(s["mu_capture"]), float(s["mu_backoff"]), float(s["mu_search"]),
            s["stabilizer"] == "half")


# ------------------------------------------------------- design sweeps
def suggested_threshold(p: int, w_max: int) -> float:
    """The simulator's operating point: a quarter of the saturated
    potential of uniform weights, ``p * w_max / 8`` (at least 1)."""
    return max(1.0, 0.25 * p * w_max / 2.0)


def explore_init(seed: int, index: int, p: int, q: int, w_max: int):
    """Initial weights of candidate ``index`` of an exploration seeded
    ``seed``: uniform on ``[0, w_max)``, keyed by (seed, candidate)."""
    _, init_key = jax.random.split(jax.random.key(seed))
    return jax.random.uniform(
        jax.random.fold_in(init_key, index), (p, q), jnp.float32, 0.0,
        float(w_max),
    )


def rand_index(labels_true, labels_pred) -> float:
    """Unadjusted Rand index from the contingency table."""
    a = np.asarray(labels_true).ravel()
    b = np.asarray(labels_pred).ravel()
    n = a.size
    if n < 2:
        return 1.0
    _, ai = np.unique(a, return_inverse=True)
    _, bi = np.unique(b, return_inverse=True)
    cont = np.zeros((ai.max() + 1, bi.max() + 1), np.int64)
    np.add.at(cont, (ai, bi), 1)
    comb_c = (cont * (cont - 1) // 2).sum()
    comb_a = (cont.sum(1) * (cont.sum(1) - 1) // 2).sum()
    comb_b = (cont.sum(0) * (cont.sum(0) - 1) // 2).sum()
    total = n * (n - 1) // 2
    return float((comb_c + total - comb_a - comb_b + comb_c) / total)


# ------------------------------------------------------------- service
def envelope_buckets(shapes, waste_cap: float = 4.0):
    """Which designs share a padding envelope (and so a batch queue and a
    re-fit counter): greedy, largest fire volume ``p * q * t_max`` first;
    a design joins a bucket while the envelope keeps every member within
    ``waste_cap`` of its own volume.  Returns lists of design indices."""
    vols = [p * q * t for (p, q, t) in shapes]
    buckets: list[tuple[tuple, list[int]]] = []
    for i in sorted(range(len(shapes)), key=lambda i: -vols[i]):
        p, q, t = shapes[i]
        for bi, (env, members) in enumerate(buckets):
            cand = (max(env[0], p), max(env[1], q), max(env[2], t))
            vol = cand[0] * cand[1] * cand[2]
            if all(vol <= waste_cap * vols[m] for m in members + [i]):
                buckets[bi] = (cand, members + [i])
                break
        else:
            buckets.append(((p, q, t), [i]))
    return [members for _, members in buckets]


class ServiceReplay:
    """The clustering service's documented semantics, replayed.

    Requests queue per envelope bucket; a queue that reaches
    ``batch_size`` runs as one batch, and ``flush`` runs every queue in
    batches.  Each answer uses the weights live when its batch runs.
    After a batch, a bucket that has served ``refit_every`` requests since
    its last re-fit trains each of its designs, for ``refit_epochs``
    passes, on the last ``refit_window`` volleys that design served since
    then, in order; then the counters and buffers start again.

    ``designs`` holds per design ``(p, q, t_max, threshold)``; ``weights``
    the initial ``[p, q]`` arrays; ``encodes`` per design the spike times
    of its whole stream.  Answers are not computed here: each submitted
    request records ``(design, series, version)``, the index of the
    weights it was answered with in ``self.versions[design]``.
    """

    def __init__(self, designs: dict, weights: dict, encodes: dict, *,
                 batch_size: int, refit_every: int, refit_window: int,
                 refit_epochs: int, statics: tuple, waste_cap: float = 4.0,
                 dtype=jnp.float32):
        self.designs = designs
        self.encodes = encodes
        self.batch_size = batch_size
        self.refit_every = refit_every
        self.refit_window = refit_window
        self.refit_epochs = refit_epochs
        self.statics = statics
        self.dtype = dtype
        names = list(designs)
        shapes = [designs[n][:3] for n in names]
        self.bucket_of = {}
        self.buckets = []
        for members in envelope_buckets(shapes, waste_cap):
            b = {"names": [names[i] for i in members], "queue": [],
                 "served": 0}
            self.buckets.append(b)
            for i in members:
                self.bucket_of[names[i]] = b
        self.buffers = {n: [] for n in names}
        self.versions = {n: [jnp.asarray(weights[n], dtype)] for n in names}
        self.requests: list[list] = []
        self.trained = {n: 0 for n in names}  # volleys each design trained on

    def submit(self, design: str, series: int) -> None:
        b = self.bucket_of[design]
        b["queue"].append(len(self.requests))
        self.requests.append([design, series, -1])
        if len(b["queue"]) >= self.batch_size:
            self._run(b)

    def flush(self) -> None:
        for b in self.buckets:
            while b["queue"]:
                self._run(b)

    def _run(self, b: dict) -> None:
        batch = b["queue"][: self.batch_size]
        del b["queue"][: self.batch_size]
        for r in batch:
            design, series, _ = self.requests[r]
            self.requests[r][2] = len(self.versions[design]) - 1
            b["served"] += 1
            buf = self.buffers[design]
            buf.append(series)
            del buf[: max(0, len(buf) - self.refit_window)]
        if b["served"] >= self.refit_every and any(
            self.buffers[n] for n in b["names"]
        ):
            for n in b["names"]:
                if self.buffers[n]:
                    self._refit(n)
                self.buffers[n] = []
            b["served"] = 0

    def _refit(self, design: str) -> None:
        p, q, t_max, threshold = self.designs[design]
        xs = np.full((self.refit_window, p), t_max, np.int32)
        buf = self.buffers[design]
        xs[: len(buf)] = np.asarray(self.encodes[design])[buf]
        self.trained[design] += len(buf)
        w = fit(
            self.versions[design][-1], jnp.asarray(xs),
            jnp.float32(threshold), t_max=t_max, epochs=self.refit_epochs,
            statics=self.statics, dtype=self.dtype,
        )
        self.versions[design].append(w)
