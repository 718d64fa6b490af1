"""Program spans on the profiler's clock (``repro.obs.span``): a reduced
serving batch with a replica failover, traced on the CPU, writes every
span of the serving path with its expected count, nested as
docs/obs_api.md's table says."""
import glob
from collections import defaultdict

import numpy as np
import pytest

from repro.obs import profiler, span

KILL_AT, N_GEN = 3, 6


@pytest.fixture(scope="module")
def traced_batch(tmp_path_factory):
    """Two batches of a reduced server, the second traced: the host
    plane's ``repro.*`` events as {name: [(start, end, stats)]}."""
    import jax
    from jax.profiler import ProfileData
    from repro.launch.serve import ReplicatedServer
    srv = ReplicatedServer("codeqwen1.5-7b", batch=2, prompt_len=16)
    prompts = np.random.default_rng(0).integers(0, 400, (2, 16),
                                                dtype=np.int32)
    srv.generate(prompts, N_GEN, kill_at=KILL_AT)       # compiles
    out = tmp_path_factory.mktemp("trace")
    with jax.profiler.trace(str(out)):
        tokens = srv.generate(prompts, N_GEN, kill_at=KILL_AT)
    assert tokens.shape == (2, N_GEN) and srv.promotions == 2
    path, = glob.glob(str(out / "**" / "*.xplane.pb"), recursive=True)
    events = defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("repro."):
                    events[e.name].append((e.start_ns,
                                           e.start_ns + e.duration_ns,
                                           dict(e.stats)))
    return events


def test_every_span_of_the_serving_path_with_its_count(traced_batch):
    counts = {name: len(v) for name, v in traced_batch.items()}
    assert counts == {
        "repro.serve.generate": 1,
        "repro.serve.session": 1,
        "repro.serve.fanout": 1,
        "repro.ft.start": 1,
        "repro.ft.replica_copy": 1,           # on_start; none after promote
        "repro.ft.intake": N_GEN + 1,         # every step's poll, the kill
        "repro.ft.recover.promote": 1,
        "repro.ft.step": N_GEN,
        "repro.ft.replica_step": KILL_AT,     # the replica runs until promoted
        "repro.workload.prefill": 1,
        "repro.workload.host_copy": N_GEN + KILL_AT,
        "repro.workload.decode": N_GEN + KILL_AT,
        "repro.workload.sample": N_GEN + KILL_AT,
    }
    (_, _, stats), = traced_batch["repro.serve.generate"]
    assert stats == {"batch": 2}


def _inside(inner, outer):
    return any(s <= inner[0] and inner[1] <= e for s, e, _ in outer)


@pytest.mark.parametrize("child,parent", [
    ("repro.serve.session", "repro.serve.generate"),
    ("repro.serve.fanout", "repro.serve.generate"),
    ("repro.ft.start", "repro.serve.generate"),
    ("repro.ft.intake", "repro.serve.generate"),
    ("repro.ft.recover.promote", "repro.serve.generate"),
    ("repro.ft.step", "repro.serve.generate"),
    ("repro.workload.prefill", "repro.ft.start"),
    ("repro.ft.replica_copy", "repro.ft.start"),
    ("repro.ft.replica_step", "repro.ft.step"),
    ("repro.workload.host_copy", "repro.ft.step"),
    ("repro.workload.decode", "repro.ft.step"),
    ("repro.workload.sample", "repro.ft.step"),
])
def test_spans_nest_by_layer(traced_batch, child, parent):
    assert all(_inside(c, traced_batch[parent]) for c in traced_batch[child])


def test_driver_spans_do_not_overlap_one_another(traced_batch):
    """start, intake, recovery and step take turns inside the batch."""
    driver = sorted((s, e) for name in ("repro.ft.start", "repro.ft.intake",
                                        "repro.ft.recover.promote",
                                        "repro.ft.step")
                    for s, e, _ in traced_batch[name])
    assert all(a[1] <= b[0] for a, b in zip(driver, driver[1:]))


def test_span_without_jax_is_a_null_context(monkeypatch):
    """The simulator's numpy-only environment still imports repro.obs."""
    import sys
    monkeypatch.setitem(sys.modules, "jax.profiler", None)
    profiler._factory.cache_clear()
    try:
        with span("repro.ft.step", batch=1) as got:
            assert got is None
    finally:
        monkeypatch.undo()
        profiler._factory.cache_clear()
    from jax.profiler import TraceAnnotation
    assert isinstance(span("repro.ft.step"), TraceAnnotation)
