"""Closed-loop batched serving through ``ReplicatedServer.generate``.

One client sends a batch, waits for all of its tokens, and sends the next:
each sequence of a batch is one request.  The traffic file fixes the batch,
the prompt length, the number of new tokens, the token after which the
computational slice is killed (so every batch fails over to its replica at
the same place), and the pool of distinct prompt batches drawn from the
seed that the window cycles through.  Encoder-decoder models get seeded
frame embeddings in place of the stub frontend's zeros, so the encoder's
output depends on its weights.

Timing, on the host clock:
  * a token's time is when it reaches the host: the served path copies each
    token to the host before it dispatches the next decode call, and the
    client stamps the first decode call of each step (the computational
    copy's);
  * TTFT of a request is its batch's dispatch to its first token; the gaps
    between consecutive tokens of a sequence, the one across the failover
    included, are its inter-token latencies;
  * tokens/s is every generated token of the window over the window, which
    holds whole batches and lasts ``seconds`` or a little more.

After the window the served tokens of a seeded sample of requests are
scored by the configuration's plain reference (see ``check``).
"""
from __future__ import annotations

import contextlib
import gc
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np

from bench import harness, trace_reduce
from bench.compile_log import CompileLog

WINDOW = "bench.window"


def jax_seed(seed: int) -> int:
    return seed % 2**31


def span(on: bool, name: str):
    return jax.profiler.TraceAnnotation(name) if on \
        else contextlib.nullcontext()


def prompt_pool(seed: int, vocab: int, n: int, batch: int, length: int):
    return np.random.default_rng(seed).integers(
        0, vocab, (n, batch, length), dtype=np.int32)


def frame_pool(seed: int, n: int, batch: int, frames: int, d_model: int):
    """Frame embeddings on the device, in one jitted call from the seed."""
    def make(key):
        return jax.random.normal(key, (n, batch, frames, d_model),
                                 jnp.float32).astype(jnp.bfloat16)
    return jax.jit(make)(jax.random.fold_in(jax.random.key(jax_seed(seed)),
                                            1))


class TokenClock:
    """Wraps the server's decode call: stamps the first call of each step,
    which comes right after that step's token reached the host."""

    def __init__(self, decode, trace: bool):
        self.decode = decode
        self.trace = trace
        self.times = []
        self._due = False

    def wrap_step(self, step):
        def wrapped(state, t):
            self._due = t == len(self.times)
            return step(state, t)
        return wrapped

    def __call__(self, *args):
        if self._due:
            self.times.append(time.perf_counter())
            self._due = False
        with span(self.trace, "bench.decode"):
            return self.decode(*args)


class GcPauses:
    """Python's garbage collections while it is on: generation and
    seconds of each, to tell a stalled batch's cause."""

    def __init__(self):
        self.pauses, self._t0 = [], None

    def __call__(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.pauses.append((info["generation"],
                                time.perf_counter() - self._t0))

    def __enter__(self):
        gc.callbacks.append(self)
        return self

    def __exit__(self, *exc):
        gc.callbacks.remove(self)

    def summary(self) -> dict:
        return {f"gen{g}": {"count": sum(1 for k, _ in self.pauses if k == g),
                            "longest_ms": round(1e3 * max(
                                [d for k, d in self.pauses if k == g],
                                default=0.0), 3)} for g in (0, 1, 2)}


class Client:
    """The client's side of the server: instruments it and sends batches."""

    def __init__(self, srv, traffic: dict, prompts, frames, trace: bool):
        self.srv, self.traffic = srv, traffic
        self.prompts, self.frames, self.trace = prompts, frames, trace
        self.clock = TokenClock(srv.decode, trace)
        srv.decode = self.clock
        prefill, fan_out = srv.prefill, srv.fanout.fan_out
        make_workload = srv.workload
        self._frames_now = None

        def traced_prefill(*args):
            with span(trace, "bench.prefill"):
                return prefill(*args)

        def traced_fan_out(batch):
            with span(trace, "bench.fanout"):
                return fan_out(batch)

        def workload(prompt_tokens):
            wl = make_workload(prompt_tokens)
            if self._frames_now is not None:
                wl.batch["frames"] = self._frames_now
            wl.step = self.clock.wrap_step(wl.step)
            return wl

        srv.prefill = traced_prefill
        srv.fanout.fan_out = traced_fan_out
        srv.workload = workload

    def send(self, i: int) -> dict:
        """Batch ``i`` of the cycle through the prompt pool."""
        tr = self.traffic
        k = i % len(self.prompts)
        self._frames_now = None if self.frames is None else self.frames[k]
        self.clock.times = []
        promotions = self.srv.promotions
        with span(self.trace, "bench.batch"):
            t0 = time.perf_counter()
            tokens = self.srv.generate(self.prompts[k], tr["new_tokens"],
                                       kill_at=tr["kill_at_token"])
            tokens = np.asarray(tokens)
        return {"pool": k, "tokens": tokens, "sent": t0,
                "done": time.perf_counter(),
                "token_times": np.asarray(self.clock.times),
                "promotions": self.srv.promotions - promotions}


def latency_metrics(batches: list, batch_size: int, kill_at: int,
                    t_open: float, t_close: float) -> dict:
    """Each sequence of a batch is a request with its batch's times; a
    request's failover gap is its gap from the token before the kill to
    the one after it."""
    ttft = np.repeat([b["token_times"][0] - b["sent"] for b in batches],
                     batch_size) * 1e3
    itl = np.concatenate([np.tile(np.diff(b["token_times"]), batch_size)
                          for b in batches]) * 1e3
    failover = np.repeat([np.diff(b["token_times"])[kill_at - 1]
                          for b in batches], batch_size) * 1e3
    tokens = sum(b["tokens"].size for b in batches)
    return {"serve_tokens_per_s": tokens / (t_close - t_open),
            "itl_p95_ms": float(np.percentile(itl, 95)),
            "ttft_p95_ms": float(np.percentile(ttft, 95)),
            "failover_gap_p95_ms": float(np.percentile(failover, 95)),
            "samples": {"requests": int(ttft.size), "itl_gaps": int(itl.size),
                        "tokens": int(tokens)}}


def batch_breakdown(batches: list, kill_at: int) -> list:
    """Per batch, in ms on the host clock: dispatch to first token, the
    mean gap before the kill (two decode calls a token), the gap across the
    failover, the mean gap after it, the last token to the batch's end,
    and the whole batch."""
    rows = []
    for b in batches:
        t = b["token_times"]
        gaps = np.diff(t)
        rows.append([round(1e3 * float(v), 3) for v in (
            t[0] - b["sent"], gaps[:kill_at - 1].mean(), gaps[kill_at - 1],
            gaps[kill_at:].mean(), b["done"] - t[-1], b["done"] - b["sent"])])
    return rows


def sample_requests(seed: int, batches: list, batch_size: int, k: int):
    """``k`` distinct (batch, row) pairs drawn from the seed; every request
    of the mix has the same length, so each sample holds the longest."""
    n = len(batches) * batch_size
    ids = np.random.default_rng([seed, 7]).choice(n, size=min(k, n),
                                                  replace=False)
    return [(int(i) // batch_size, int(i) % batch_size) for i in sorted(ids)]


def check(cell, seed: int, batches: list, prompts, frames, *,
          control: bool = False) -> dict:
    """Over a seeded sample of finished requests, the gap by which each
    judged token's logit lies below the best logit of the plain float32
    reference, run once over the prompt and the served tokens:
    ``widest_logit_gap`` is the widest gap, ``worst_request_mean_gap`` the
    largest of the requests' mean gaps.  ``served`` judges the served
    tokens; with ``control`` also ``control``, which judges the tokens that
    the fp8 control, run over the same prompts and tokens, puts first."""
    from bench.reference.common import Numerics, served_token_gaps
    ref = harness.reference(cell.config)
    model, tr = cell.model, cell.traffic
    p = tr["prompt_len"]
    picks = sample_requests(seed, batches, tr["batch"], tr["check_requests"])
    with jax.default_matmul_precision("highest"):
        # drawn op by op, as the served model draws its weights: a jitted
        # draw fuses the scaling and rounds a few elements differently
        params = jax.tree.map(lambda a: a.astype(jnp.float32),
                              ref.init_params(model, jax_seed(seed)))

        def scored(num):
            def f(params, toks, fr):
                return ref.logits(params, model, toks, fr, num)[0, p - 1:]
            return jax.jit(f)

        f32, fp8 = scored(Numerics("f32")), scored(Numerics("fp8"))
        gaps, gaps_ctl = [], []
        for bi, row in picks:
            b = batches[bi]
            served = b["tokens"][row]
            toks = jnp.asarray(np.concatenate(
                [prompts[b["pool"]][row], served[:-1]])[None])
            fr = None if frames is None else frames[b["pool"]][row][None]
            lg = f32(params, toks, fr)
            gaps.append(np.asarray(served_token_gaps(lg,
                                                     jnp.asarray(served))))
            if control:
                first = jnp.argmax(fp8(params, toks, fr), -1)
                gaps_ctl.append(np.asarray(served_token_gaps(lg, first)))
    out = {"served": {"requests": len(picks),
                      "tokens": sum(g.size for g in gaps),
                      **_gap_readings(gaps)}}
    if control:
        out["control"] = _gap_readings(gaps_ctl)
    return out


def _gap_readings(per_request: list) -> dict:
    return {"widest_logit_gap": float(max(g.max() for g in per_request)),
            "worst_request_mean_gap": float(max(g.mean()
                                                for g in per_request))}


def serve(cell, seed: int, seconds: float, trace: bool, *, t_start=None,
          n_batches=None, control=False) -> dict:
    """One run of the cell: set-up and warm-up, a window of ``seconds`` (or
    of ``n_batches`` batches), the check.  With ``control`` the fp8
    control takes the served tokens' place in the check, so ``correct``
    says whether the check catches it (``bench/control.py``)."""
    from repro.launch.serve import ReplicatedServer
    t_start = time.perf_counter() if t_start is None else t_start
    compiles = CompileLog()
    tr = cell.traffic
    model = cell.model
    srv = ReplicatedServer(cell.config["arch"], reduced=False,
                           batch=tr["batch"], prompt_len=tr["prompt_len"],
                           replication=True, seed=jax_seed(seed))
    prompts = prompt_pool(seed, srv.cfg.vocab_size, tr["prompt_pool"],
                          tr["batch"], tr["prompt_len"])
    frames = None
    if srv.cfg.is_encoder_decoder:
        frames = frame_pool(seed, tr["prompt_pool"], tr["batch"],
                            srv.cfg.n_frames, srv.cfg.d_model)
    client = Client(srv, tr, prompts, frames, trace)
    # warm-up: every shape and path the window takes, failover included
    for i in range(tr["warmup_batches"]):
        client.send(i)
    setup = compiles.take()
    harness.say(f"setup compiles: {setup}")

    trace_dir = tempfile.TemporaryDirectory() if trace else None
    if trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(trace_dir.name, profiler_options=opts)
    batches = []
    with span(trace, WINDOW), GcPauses() as gc_pauses:
        t_open = time.perf_counter()
        setup_s = t_open - t_start
        i = 0
        while True:
            batches.append(client.send(i))
            i += 1
            done = time.perf_counter()
            if (n_batches is not None and i >= n_batches) or \
                    (n_batches is None and done - t_open >= seconds):
                break
        t_close = done
    reduction = None
    if trace:
        jax.profiler.stop_trace()
        reduction = trace_reduce.reduce_trace(trace_dir.name)
        trace_dir.cleanup()
    in_window = compiles.take()
    harness.say(f"window compiles: {in_window}")
    if in_window["backend_compiles"] or in_window["persistent_cache_hits"]:
        raise harness.HarnessError(f"compiled inside the window: {in_window}")

    lat = latency_metrics(batches, tr["batch"], tr["kill_at_token"], t_open,
                          t_close)
    stats = jax.devices()[0].memory_stats() or {}
    peak = int(stats.get("peak_bytes_in_use", 0))
    promotions = [b["promotions"] for b in batches]
    harness.say(f"window: {t_close - t_open:.6f} s, {len(batches)} batches, "
                f"samples {lat['samples']}, promotions per batch "
                f"{sorted(set(promotions))}, peak device bytes {peak}")
    harness.say(f"window garbage collections: {gc_pauses.summary()}")
    harness.say("per batch, ms (first token, gap before kill, failover "
                "gap, gap after, tail, batch): "
                f"{batch_breakdown(batches, tr['kill_at_token'])}")
    # free the server and its caches before the reference takes the chip
    del client, srv
    gc.collect()

    got = check(cell, seed, batches, prompts, frames, control=control)
    finished = all(b["tokens"].shape == (tr["batch"], tr["new_tokens"])
                   for b in batches)
    vocab = model["vocab_size"]
    outside = sum(int(((b["tokens"] < 0) | (b["tokens"] >= vocab)).sum())
                  for b in batches)
    missed = sum(1 for p in promotions if p != 1)
    # the numbers compared are those the cell's limits file names
    judged = got["control"] if control else got["served"]
    checks = {name: {"value": judged[name], "limit": lim["limit"]}
              for name, lim in cell.limits.items()}
    checks["tokens_outside_vocabulary"] = {"value": outside, "limit": 0}
    checks["batches_without_one_failover"] = {"value": missed, "limit": 0}
    harness.say(f"check: {got}")
    correct = finished and all(
        c["limit"] is not None and c["value"] <= c["limit"]
        for c in checks.values())
    attempted = len(batches) * tr["batch"]
    return {
        "correct": correct, "attempted": attempted,
        "failed": 0 if finished else attempted, "checks": checks,
        "end_to_end": {"setup_s": setup_s, **lat},
        "memory_peak_bytes": peak, "trace": reduction,
        "readings": got["served"], "control_readings": got.get("control"),
        "record": {"trace": reduction, "model": model, "traffic": tr,
                   "family": cell.config["family"],
                   "peaks": harness.peaks(jax.devices()[0].device_kind)
                   if trace else None},
    }


def run(cell, *, seed: int, seconds: float, trace: bool, t_start) -> dict:
    n = cell.traffic["trace_batches"] if trace else None
    return serve(cell, seed, seconds, trace, t_start=t_start, n_batches=n)
