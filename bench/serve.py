"""Serving cells: a compressed checkpoint served through ``Engine`` and the
continuous-batching ``Scheduler`` under the mix's open-loop arrivals.

Set-up makes the dense model on the device from the seed, compresses it
with the mix's policy (``plan_compression`` / ``execute_plan``), builds the
engine on the fused bit-linear path and the scheduler over the paged KV
cache, compiles the prefill of every prompt length of the mix and the
decode step, then submits the mix's opening backlog and steps until every
slot is busy.  The window submits each later request when it is due and
steps the scheduler until ``seconds`` have passed.  ``output_tokens_per_s``
is every token the scheduler emitted in the window's steps (first tokens
and decode tokens) over the time from the window's start to the end of its
last step.

``correct``: the logits that the timed path computed for each token it
served to the backlog's requests are kept on the device; once the window is
read, a sample of those requests that finished, drawn from the seed with
the longest among them, is run through the float32 reference
(``bench/serve_reference.py``) over its prompt and served tokens, and
compared at every served position.  The reference serves the program's
``m_packed`` and ``C``; so that it takes nothing unchecked from the
program, every served tile's ``M C`` is first held against the seeded
dense weights by ``bench/reference.py`` (``c_gap``, ``resid_gap``: ``C``
against the least-squares factor of the dense tile for its ``M``).

The mix (``bench/traffic/<name>.json``, kind ``serve``) gives the policy,
the engine's slots, ``max_len`` and page size, and the request schedule.
Every block of ``block`` requests holds the same sizes: the mid-quantiles
of the prompt and output length distributions (rounded to whole tokens)
and of the exponential gaps between arrivals at ``rate_per_s``; the seed
orders each block and draws the token ids, so two seeds do the same work
in another order.  The first ``backlog`` requests arrive in set-up.
"""

from __future__ import annotations

import gc
import math
import statistics
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference, serve_reference, weights
from bench.cells import model_config
from bench.compress import policy
from bench.device import log

# A traced run profiles this much of the window: a 15 s serving trace took
# ~50 s to write and read on the chip, so a whole 45 s window's would
# crowd a run's time limit.
TRACE_SECONDS = 15.0


def _words(*parts: int) -> list:
    """Non-negative 32-bit words for numpy's seed sequence (seeds may be
    wider than 32 bits)."""
    out = []
    for p in parts:
        out += [p & 0xFFFFFFFF, (p >> 32) & 0xFFFFFFFF]
    return out


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` mid-quantiles of a length distribution of the mix,
    clipped to ``min``/``max`` and rounded to whole tokens."""
    q = (np.arange(n) + 0.5) / n
    if spec["dist"] == "loguniform":
        lo, hi = math.log(spec["min"]), math.log(spec["max"])
        x = np.exp(lo + q * (hi - lo))
    elif spec["dist"] == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(v) for v in q])
        x = spec["median"] * np.exp(spec["sigma"] * z)
    else:
        raise ValueError(f"no length distribution {spec['dist']!r}")
    return np.rint(np.clip(x, spec["min"], spec["max"])).astype(int)


class Schedule:
    """The mix's requests from a seed: ``request(i)`` is (prompt ids,
    output tokens) and ``arrival(j)`` the offset in seconds of the j-th
    request after the backlog from the window's start."""

    def __init__(self, traffic: dict, seed: int, vocab: int):
        self.seed, self.vocab = seed, vocab
        self.n = traffic["block"]
        self.backlog = traffic["arrivals"]["backlog"]
        self.prompt = quantiles(traffic["prompt_len"], self.n)
        self.output = quantiles(traffic["output_len"], self.n)
        q = (np.arange(self.n) + 0.5) / self.n
        self.gaps = -np.log1p(-q) / traffic["arrivals"]["rate_per_s"]
        self._blocks: dict = {}
        self._times = [0.0]

    def _block(self, b: int):
        if b not in self._blocks:
            rng = np.random.default_rng(_words(self.seed, b))
            self._blocks[b] = tuple(rng.permutation(self.n) for _ in range(3))
        return self._blocks[b]

    def request(self, i: int):
        b, k = divmod(i, self.n)
        perm_p, perm_o, _ = self._block(b)
        rng = np.random.default_rng(_words(self.seed, 1 << 40 | i))
        prompt = rng.integers(0, self.vocab, self.prompt[perm_p[k]],
                              dtype=np.int32)
        return prompt, int(self.output[perm_o[k]])

    def arrival(self, j: int) -> float:
        while len(self._times) <= j:
            b, k = divmod(len(self._times) - 1, self.n)
            self._times.append(self._times[-1] + self.gaps[self._block(b)[2][k]])
        return self._times[j]


# Tiles per least-squares call of the check: the batched SVD behind it
# overflows the TPU v5e's scoped vector memory from 16,384 tiles of 32 x 4
# (an expert stack holds 98,304).
TILE_BLOCK = 4096


def _blocks(n: int) -> list:
    return [slice(i, i + TILE_BLOCK) for i in range(0, n, TILE_BLOCK)]


def _control_C(W, M):
    """The control of one tensor's served tiles: for the program's M, the
    least-squares factor of the float8-rounded tiles rounded to float8
    (per-tensor scale), and the residual it records against the rounded
    tiles."""
    Wq = reference._q8(W)
    C = reference._q8(jnp.concatenate(
        [reference._lstsq_C(M[s], Wq[s]) for s in _blocks(W.shape[0])]))
    return C, jnp.sqrt(reference._sq_resid(M, C, Wq))


def _pct(values, p: float):
    return float(np.percentile(values, p)) if len(values) else None


class ServeCell:
    def __init__(self, cell, seed: int):
        self.cell, self.seed = cell, seed
        self.cfg = model_config(cell.config)
        self.traffic = cell.traffic
        self.engine_spec = self.traffic["engine"]
        self.sched = None

    # -- set-up -------------------------------------------------------------

    def compress(self):
        """The seeded dense model compressed by the mix's policy: the
        program's (params, artifact).  Keeps each compressed tensor's plan,
        ``m_packed``, ``C`` and recorded tile residuals for the check."""
        from repro.compression import execute_plan, plan_compression

        values = weights.dense(self.cfg, self.seed)
        plan = plan_compression(values, policy(self.traffic["policy"]))
        if not plan.tensors:
            raise ValueError("the mix's policy compresses no tensor")
        params, artifact = execute_plan(plan, values,
                                        key=weights.seed_key(self.seed))
        flat = dict(weights.paths(params))
        self.compressed = {
            t.path: (t, flat[t.path + "/m_packed"], flat[t.path + "/C"],
                     artifact.manifest["tensors"][t.path]["tile_resid"])
            for t in plan.tensors}
        return params, artifact

    def setup(self, trace: bool) -> None:
        from repro.serving import Scheduler
        from repro.serving.engine import Engine

        self.free()
        marks = [time.perf_counter()]
        params, artifact = self.compress()
        marks.append(time.perf_counter())
        spec = self.engine_spec
        # eos -1: no token ends a request early; each runs to its length.
        engine = Engine(self.cfg, params, max_len=spec["max_len"],
                        batch=spec["num_slots"], artifact=artifact, eos_id=-1)
        if not engine.fused_bitlinear:
            raise RuntimeError("the engine is not on the fused bit-linear path")
        self.sched = Scheduler(engine, num_slots=spec["num_slots"],
                               page_size=spec["page_size"],
                               max_len=spec["max_len"])
        self.requests = []                   # (Request, intended arrival)
        self.tracked = set()
        self.rejected = 0
        self._keep_logits()
        self.schedule = Schedule(self.traffic, self.seed, self.cfg.vocab_size)
        # Every prompt length of the mix (one prefill program each) and the
        # decode step, compiled.
        self.sched.generate_batch(
            [np.full(n, 3, np.int32) for n in sorted(set(self.schedule.prompt))],
            max_tokens=2)
        self.sched.stats.reset()
        marks.append(time.perf_counter())
        for i in range(self.schedule.backlog):
            req = self._submit(i, time.perf_counter())
            if req is not None:
                self.tracked.add(req.rid)
        while self.sched.pending or any(
                r is not None and r.state == "prefill"
                for r in self.sched.slot_req):
            self.sched.step()
        marks.append(time.perf_counter())
        log("[serve] set-up: weights and compression {:.3f} s, engine and "
            "warm-up {:.3f} s, backlog {:.3f} s".format(
                *(b - a for a, b in zip(marks, marks[1:]))))

    def _submit(self, i: int, due: float):
        """Request ``i`` of the schedule, due at ``due``; None where the
        program rejects it (counted as failed)."""
        prompt, out = self.schedule.request(i)
        try:
            req = self.sched.submit(prompt, out, eos_id=-1)
        except ValueError as e:
            log(f"[serve] request {i} rejected: {e}")
            self.rejected += 1
            return None
        self.requests.append((req, due))
        return req

    def _keep_logits(self) -> None:
        """Keep, for each tracked request, the logits the timed path computed
        for every token it served: the prefill's last row (the row the
        scheduler samples the first token from) and each decode step's
        output, read after the window."""
        sched = self.sched
        self.steps, self.rows, self.decodes = [], {}, []
        decode, sample = sched._decode_fn, sched._sample

        def decode_fn(*args):
            out = decode(*args)
            running = [(s, r) for s, r in enumerate(sched.slot_req)
                       if r is not None and r.state == "running"]
            self.decodes.append((time.perf_counter(), len(running), sum(
                len(r.prompt) + len(r.tokens) for _, r in running)))
            mine = [(s, r) for s, r in running if r.rid in self.tracked]
            if mine:
                self.steps.append(out[0])
                for s, r in mine:
                    self.rows[r.rid][len(r.tokens)] = (len(self.steps) - 1, s)
            return out

        def sample_fn(req, logits_row, index):
            if req.rid in self.tracked:
                self.rows.setdefault(req.rid, {})[index] = logits_row
            return sample(req, logits_row, index)

        sched._decode_fn, sched._sample = decode_fn, sample_fn

    # -- window ---------------------------------------------------------------

    def window(self, seconds: float, trace_dir: str | None):
        sched, stats = self.sched, self.sched.stats
        if trace_dir:
            seconds = min(seconds, TRACE_SECONDS)
        s0 = (stats.decode_steps, stats.decode_tokens)
        emitted0 = sum(len(r.tokens) for r, _ in self.requests)
        j = 0
        ends = []
        if trace_dir:
            jax.profiler.start_trace(trace_dir)
        t0 = time.perf_counter()
        deadline = t0 + seconds
        with jax.profiler.TraceAnnotation("bench.window"):
            while True:
                now = time.perf_counter()
                if now >= deadline:
                    break
                while t0 + self.schedule.arrival(j) <= now:
                    self._submit(self.schedule.backlog + j,
                                 t0 + self.schedule.arrival(j))
                    j += 1
                if sched.has_work():
                    with jax.profiler.TraceAnnotation("bench.step"):
                        sched.step()
                    ends.append(time.perf_counter())
                else:
                    time.sleep(min(t0 + self.schedule.arrival(j), deadline)
                               - now)
        if trace_dir:
            jax.profiler.stop_trace()
        t_end = ends[-1] if ends else time.perf_counter()
        emitted = sum(len(r.tokens) for r, _ in self.requests) - emitted0
        steps = stats.decode_steps - s0[0]
        return {"t0": t0, "t_end": t_end, "emitted": emitted,
                "decode_steps": steps, "arrived": j,
                "occupancy": (stats.decode_tokens - s0[1]) / steps
                if steps else None,
                "work": self._work(t0, t_end)}

    def _work(self, t0: float, t1: float) -> dict:
        """What the model processed in [t0, t1): ``tokens`` (the prompt
        tokens of each prefill that served its first token there, and one
        per running slot of each decode step), ``context`` (the keys each
        of those tokens attends to, itself included) and ``rows`` (the
        logits rows that served a token)."""
        tokens = ctx = rows = 0
        for r, _ in self.requests:
            if r.tokens and t0 <= r.t_first_token < t1:
                P = len(r.prompt)
                tokens += P
                ctx += P * (P + 1) // 2
                rows += 1
        for t, n, c in self.decodes:
            if t0 <= t < t1:
                tokens += n
                ctx += c
                rows += n
        return {"tokens": tokens, "context": ctx, "rows": rows}

    # -- readings -------------------------------------------------------------

    def end_to_end(self, w) -> dict:
        span = w["t_end"] - w["t0"]
        done = [(r, due) for r, due in self.requests if r.state == "done"]
        ttft = [r.t_first_token - due for r, due in self.requests
                if r.tokens and r.t_first_token >= w["t0"]]
        tpot = [(r.t_done - r.t_first_token) / (len(r.tokens) - 1)
                for r, _ in done if len(r.tokens) > 1 and r.t_done >= w["t0"]]
        log(f"[serve] {w['emitted']} tokens in {span:.3f} s over "
            f"{w['decode_steps']} decode steps "
            f"({span / max(w['decode_steps'], 1):.4f} s per step), mean "
            f"occupied slots {w['occupancy']}; {w['arrived']} arrivals, "
            f"{sum(r.t_done >= w['t0'] for r, _ in done)} completed, "
            f"{len(self.sched.pending)} waiting at the close")
        log(f"[serve] ttft p50 {_pct(ttft, 50)} s p95 {_pct(ttft, 95)} s "
            f"({len(ttft)}); tpot p50 {_pct(tpot, 50)} s p95 "
            f"{_pct(tpot, 95)} s ({len(tpot)})")
        return {"output_tokens_per_s": (w["emitted"] / span, "tok/s")}

    def attempted(self, w) -> tuple[int, int]:
        """Requests submitted, and those the program rejected or ended
        short of their length."""
        short = sum(r.state == "done" and len(r.tokens) != r.max_tokens
                    for r, _ in self.requests)
        return len(self.requests) + self.rejected, short + self.rejected

    def sample(self, w):
        """A sample, drawn from the seed, of the tracked requests that
        finished, with the longest of them: (prompt, served tokens, the
        program's logits at each, float32 on the host)."""
        done = [r for r, _ in self.requests
                if r.rid in self.tracked and r.state == "done"]
        done.sort(key=lambda r: r.rid)
        n = self.traffic["sample"]
        rng = np.random.default_rng(_words(self.seed, 7))
        pick = sorted(rng.choice(len(done), min(n, len(done)), replace=False))
        chosen = [done[i] for i in pick]
        longest = max(done, key=lambda r: len(r.prompt) + len(r.tokens),
                      default=None)
        if longest is not None and all(r.rid != longest.rid for r in chosen):
            chosen.append(longest)
        out = []
        for r in chosen:
            rows = []
            for k in range(len(r.tokens)):
                got = self.rows[r.rid][k]
                rows.append(self.steps[got[0]][got[1]]
                            if isinstance(got, tuple) else got)
            z = np.asarray(jnp.stack(rows).astype(jnp.float32))
            out.append((np.asarray(r.prompt), list(r.tokens), z))
        return out

    def free(self) -> None:
        self.sched = None
        self.steps, self.rows, self.decodes = [], {}, []
        gc.collect()

    def reseed(self, seed: int) -> None:
        self.seed = seed
        self.setup(trace=False)

    def check(self, samples, control: bool = False) -> dict:
        """Every number the reference reads: the served tiles against the
        seeded dense weights (``check_compression``), then the logits over
        the sampled requests (``serve_reference.compare``); with
        ``control``, also the float8 reference's, as ``control_<name>``."""
        dense = dict(weights.paths(weights.dense(self.cfg, self.seed)))
        got = self.check_compression(dense, control)
        got |= serve_reference.compare(
            self.cell.config, self._reference_weights(dense), samples,
            self.engine_spec["max_len"], control=control)
        for k, v in got.items():
            log(f"[check] {k} {v}")
        return got

    def check_compression(self, dense: dict, control: bool = False) -> dict:
        """The worst served tile's ``c_gap`` and ``resid_gap`` over every
        compressed tensor (``reference.tile_readings`` against the dense
        tile of ``dense``, the seeded model by path); with ``control``, the
        same for the float8 least-squares factor of the program's M."""
        readings, ctrl = [], []
        for t, mp, C, rec in self.compressed.values():
            W = reference.tiles_of(dense[t.path], t.tile_n, t.tile_d)
            M = reference.decode_M(mp, t.K)
            C = C.reshape(-1, t.K, t.tile_d)
            rec = jnp.asarray(rec, jnp.float32)
            for s in _blocks(W.shape[0]):
                readings.append(reference.tile_readings(W[s], M[s], C[s],
                                                        rec[s]))
            if control:
                Cq, rq = _control_C(W, M)
                ctrl += [reference.tile_readings(W[s], M[s], Cq[s], rq[s])
                         for s in _blocks(W.shape[0])]
            del W, M
        got = reference.job_numbers(readings)
        if control:
            got |= {f"control_{k}": v
                    for k, v in reference.job_numbers(ctrl).items()}
        return got

    def _reference_weights(self, dense: dict) -> dict:
        """The reference's weights: every leaf the mix leaves dense from
        ``dense`` (the seeded model by path), each compressed one as the
        program served it (``m_packed``, ``C``), named as
        ``serve_reference`` names them."""
        def leaf(path):
            if path in self.compressed:
                _, mp, C, _ = self.compressed[path]
                return {"m_packed": mp, "C": C}
            return dense[path]

        g = "groups/0/"
        return {
            "embed": dense["embed/table"],
            "final_norm": dense["final_norm/scale"],
            "layers": {
                "norm1": dense[g + "norm1/scale"],
                "norm2": dense[g + "norm2/scale"],
                **{n: leaf(g + f"attn/{n}/w") for n in ("wq", "wk", "wv", "wo")},
                "router": dense[g + "moe/router"],
                **{n: leaf(g + f"moe/{n}") for n in ("gate", "up", "down")},
            },
        }


Runner = ServeCell
