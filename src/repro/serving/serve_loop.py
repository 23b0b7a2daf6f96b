"""Batched serving: prefill + autoregressive decode with continuous cache.

Greedy/temperature sampling over the decode_step of models/transformer.py.
The HCK long-context path refreshes its Algorithm-3 summaries every
``refresh_every`` tokens (amortized O(r)/token — DESIGN.md §3).

:class:`KRRServeLoop` is the kernel-model counterpart: it drains a query
stream through a :class:`repro.serving.predict_service.ModelRegistry`,
stamping every response with the model version that served it — the
request-side half of the zero-downtime hot-swap protocol (a publish or
rollback concurrent with the loop flips responses atomically from one
version to the next, never mixing versions within a response).
"""
from __future__ import annotations

import dataclasses
import time

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import transformer as tf
from repro.models.model_zoo import make_decode_step, make_prefill_step
from repro.runtime.trace import span

Array = jax.Array


@dataclasses.dataclass
class ServedBatch:
    """One response of :class:`KRRServeLoop`: outputs + provenance.

    ``degraded`` marks a batch served from the last-good version after
    the live one failed (non-finite output, exception, or a missed
    deadline); ``retries`` counts the extra live attempts this batch
    consumed and ``failure`` is the final live-path failure message —
    the serving-side audit of the degraded-mode ladder.
    """

    z: Array                   # (q, k) predictions
    version: int               # registry version that served this batch
    latency_s: float
    degraded: bool = False
    retries: int = 0
    failure: str | None = None


@dataclasses.dataclass
class KRRServeLoop:
    """Drain query micro-batches through a versioned model registry.

    Each call to :meth:`serve` reads ONE live-version snapshot from the
    registry (see ``ModelRegistry.predict``) and serves the whole batch
    from it, so a hot swap happening between (or during) calls can never
    produce a mixed-version response.  ``responses`` keeps the
    (version, latency) trail — the serving-side evidence the hot-swap
    tests and the update bench assert on.

    Failure handling (DESIGN.md §11): every live attempt must return
    finite predictions within ``deadline_s`` (None = no deadline).  A
    failed attempt is retried up to ``max_retries`` times with
    ``backoff_s · 2^attempt`` sleeps — each retry re-reads the live
    snapshot, so a concurrent rollback/publish heals the loop mid-batch.
    When every live attempt fails, the loop DEGRADES instead of erroring:
    the batch is served from the last version that answered cleanly,
    stamped ``degraded=True`` with the live failure in
    ``ServedBatch.failure`` and counted in :meth:`stats`.  Only when
    there is no last-good version either does the failure propagate.
    """

    registry: object           # repro.serving.predict_service.ModelRegistry
    responses: list = dataclasses.field(default_factory=list)
    deadline_s: float | None = None
    max_retries: int = 2
    backoff_s: float = 0.0
    _last_good: object = dataclasses.field(default=None, repr=False)
    _failures: int = dataclasses.field(default=0, repr=False)
    _retries: int = dataclasses.field(default=0, repr=False)
    _degraded: int = dataclasses.field(default=0, repr=False)
    _deadline_misses: int = dataclasses.field(default=0, repr=False)

    def _attempt(self, entry, queries: Array) -> tuple[Array, float]:
        """One serve attempt from ``entry``; raises NumericalFailure on a
        non-finite response or a missed deadline."""
        from repro.runtime import health

        with span("serve.attempt"):
            t0 = time.perf_counter()
            try:
                z = entry.engine(queries)
                with span("serve.wait"):
                    jax.block_until_ready(z)
            except health.NumericalFailure:
                raise
            except ValueError:
                raise    # malformed batch: a caller bug, not an engine fault
            except Exception as e:
                # an engine that throws (OOM, a dead device, a poisoned jit
                # cache) enters the same retry/degraded ladder as one that
                # returns garbage
                raise health.NumericalFailure(
                    "serve", statistic="engine_error", value=type(e).__name__,
                    detail=f"version {entry.version}: {e}")
            dt = time.perf_counter() - t0
            # serving always validates its output: this is the last line of
            # defense between a poisoned model and a client (the canary gate
            # is the first), so it is NOT gated on SolveConfig.checks
            health.probe_predictions(z, force=True, stage="serve")
            if self.deadline_s is not None and dt > self.deadline_s:
                self._deadline_misses += 1
                raise health.NumericalFailure(
                    "serve", statistic="deadline_s", value=dt,
                    detail=f"budget {self.deadline_s:g}s, version "
                           f"{entry.version}")
            return z, dt

    def serve(self, queries: Array) -> ServedBatch:
        """Serve one micro-batch; record and return the stamped response."""
        from repro.runtime.health import NumericalFailure

        with span("serve", batch=len(self.responses)):
            failure: Exception | None = None
            retries = 0
            for attempt in range(self.max_retries + 1):
                entry = self.registry.live      # fresh snapshot per attempt
                if entry is None:
                    raise ValueError("registry has no live model")
                try:
                    z, dt = self._attempt(entry, queries)
                except NumericalFailure as e:
                    self._failures += 1
                    failure = e
                    retries = attempt
                    if attempt < self.max_retries and self.backoff_s > 0:
                        time.sleep(self.backoff_s * 2.0 ** attempt)
                    continue
                out = ServedBatch(z, entry.version, dt, retries=attempt,
                                  failure=str(failure) if failure else None)
                self._retries += attempt
                self._last_good = entry
                self.responses.append(out)
                return out

            # degraded mode: the live version is unservable — fall back to
            # the last version that answered cleanly, surfacing the failure
            fallback = self._last_good
            if fallback is None or fallback.version == entry.version:
                raise failure
            t0 = time.perf_counter()
            z = fallback.engine(queries)
            jax.block_until_ready(z)
            out = ServedBatch(z, fallback.version, time.perf_counter() - t0,
                              degraded=True, retries=retries,
                              failure=str(failure))
            self._retries += retries
            self._degraded += 1
            self.responses.append(out)
            return out

    def run(self, queries: Array, micro_batch: int) -> list:
        """Serve ``queries`` in ``micro_batch`` slices; return responses."""
        if micro_batch <= 0:
            raise ValueError(f"micro_batch must be >= 1, got {micro_batch}")
        return [self.serve(queries[i:i + micro_batch])
                for i in range(0, queries.shape[0], micro_batch)]

    @property
    def versions_served(self) -> list[int]:
        """Distinct versions observed, in first-served order."""
        seen: list[int] = []
        for r in self.responses:
            if r.version not in seen:
                seen.append(r.version)
        return seen

    def stats(self) -> dict:
        """Loop counters: batches, failures, retries, degraded batches,
        deadline misses, versions served."""
        return {
            "batches": len(self.responses),
            "failures": self._failures,
            "retries": self._retries,
            "degraded_batches": self._degraded,
            "deadline_misses": self._deadline_misses,
            "versions_served": self.versions_served,
        }


@dataclasses.dataclass
class ServeSession:
    """Stateful LM serving session: prefill once, decode incrementally."""

    cfg: ArchConfig
    params: dict
    max_seq: int
    caches: dict | None = None
    pos: int = 0
    # compiled decode step, built once per session: make_decode_step returns
    # a fresh closure every call, so re-wrapping it in jax.jit on each
    # decode() retraced the whole model per generation request
    _decode_fn: object = dataclasses.field(default=None, repr=False)

    def prefill(self, batch: dict) -> Array:
        """Run the prompt; initialize caches; return last-token logits."""
        logits, layer_caches = make_prefill_step(self.cfg)(self.params, batch)
        seq = jax.tree.leaves(batch)[0].shape[1]
        b = jax.tree.leaves(batch)[0].shape[0]
        hck = tf.use_hck(self.cfg, self.max_seq)
        self.caches = tf.init_decode_caches(
            self.cfg, b, self.max_seq, hck=hck, abstract=False)
        self._absorb_prefill(layer_caches, seq)
        self.pos = seq
        return logits[:, -1]

    def _absorb_prefill(self, layer_caches, seq: int):
        cfg = self.cfg
        if cfg.family in ("dense", "vlm", "audio", "moe"):
            k, v = layer_caches[0], layer_caches[1]      # (L,B,kv,S,hd)
            if "hck" in self.caches:
                hcfg = tf.hck_cfg(cfg).for_seq(self.max_seq)
                # per-layer LEARNED landmarks: the decode state must use the
                # same inducing points the prefill attention used
                lms = self.params["blocks"]["attn_hck_lm"]   # (L, lvl, r, hd)
                states = jax.vmap(
                    lambda kk, vv, lm: jax.tree.flatten(
                        tf.ab.build_hck_decode_state(kk, vv, cfg=hcfg,
                                                     landmarks=lm))[0]
                )(k, v, lms)
                names = ["window_k", "window_v", "lm_k", "sigma", "summary",
                         "win_len"]
                self.caches["hck"] = dict(zip(names, states))
            else:
                self.caches["k"] = self.caches["k"].at[:, :, :, :seq].set(k)
                self.caches["v"] = self.caches["v"].at[:, :, :, :seq].set(v)
        if cfg.ssm:
            self.caches["ssm"] = layer_caches[0]
            self.caches["conv"] = layer_caches[1]
            if cfg.family == "hybrid" and len(layer_caches) > 2:
                sk, sv = layer_caches[2], layer_caches[3]
                every = cfg.shared_attn_every
                if "shared_k" in self.caches:
                    napp = self.caches["shared_k"].shape[0]
                    idx = jnp.arange(napp) * every
                    self.caches["shared_k"] = self.caches["shared_k"].at[
                        :, :, :, :seq].set(sk[idx])
                    self.caches["shared_v"] = self.caches["shared_v"].at[
                        :, :, :, :seq].set(sv[idx])
                elif "shared_hck" in self.caches:
                    hcfg = tf.hck_cfg(cfg).for_seq(self.max_seq)
                    napp = jax.tree.leaves(
                        self.caches["shared_hck"])[0].shape[0]
                    idx = jnp.arange(napp) * every
                    lm = self.params["shared"]["attn_hck_lm"]
                    states = jax.vmap(
                        lambda kk, vv: jax.tree.flatten(
                            tf.ab.build_hck_decode_state(kk, vv, cfg=hcfg,
                                                         landmarks=lm))[0]
                    )(sk[idx], sv[idx])
                    names = ["window_k", "window_v", "lm_k", "sigma",
                             "summary", "win_len"]
                    self.caches["shared_hck"] = dict(zip(names, states))

    def decode(self, tokens: Array, *, steps: int, temperature: float = 0.0,
               key: Array | None = None) -> Array:
        """Generate ``steps`` tokens starting from ``tokens`` (B, 1[, K])."""
        if self._decode_fn is None:
            self._decode_fn = jax.jit(make_decode_step(self.cfg))
        decode_fn = self._decode_fn
        key = key if key is not None else jax.random.PRNGKey(0)
        out = [tokens]
        cur = tokens
        for i in range(steps):
            batch = {"tokens": cur, "caches": self.caches,
                     "pos": jnp.asarray(self.pos, jnp.int32)}
            logits, self.caches = decode_fn(self.params, batch)
            if self.cfg.family == "audio":
                b = logits.shape[0]
                logits = logits.reshape(b, 1, tf.N_CODEBOOKS, self.cfg.vocab)
            if temperature > 0:
                key, sub = jax.random.split(key)
                nxt = jax.random.categorical(sub, logits[:, -1] / temperature)
            else:
                nxt = jnp.argmax(logits[:, -1], axis=-1)
            cur = nxt[:, None] if nxt.ndim == 1 else nxt[:, None, :]
            out.append(cur)
            self.pos += 1
        return jnp.concatenate(out, axis=1)
