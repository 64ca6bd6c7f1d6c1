"""The serving program's own spans and build counter
(``repro.serving.trace``): off by default at the cost of one bool check,
every ``pd.*`` span entered while on, builds charged to the innermost
open span, and the two host-clock stamps every served request carries."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from conftest import reduced_params
from repro.serving import trace
from repro.serving.cluster import ServeRequest
from repro.serving.frontend import ClusterFrontend

SPANS = {"pd.gateway.place", "pd.prefill.batch", "pd.xfer.begin",
         "pd.xfer.pump", "pd.xfer.scatter", "pd.xfer.admit",
         "pd.decode.step", "pd.decode.upload", "pd.decode.readback"}


def _frontend():
    cfg, params = reduced_params("granite-3-8b")
    return cfg, ClusterFrontend(cfg, topology={"default": (1, 1)},
                                params=params)


def _wave(fe, cfg, seed, rid0, lens=(9, 14, 6), max_new=3):
    """Timed arrivals served to completion; prompts start with a token of
    their own so that no wave hits another's cached prefix."""
    rng = np.random.default_rng(seed)
    reqs = [ServeRequest(
        rid=rid0 + i,
        tokens=[rid0 + i + 1] + rng.integers(0, cfg.vocab_size,
                                             n - 1).tolist(),
        max_new_tokens=max_new) for i, n in enumerate(lens)]
    for r in reqs:
        fe.submit(r, at=fe.now)
    fe.serve(watch=reqs)
    assert all(r.done and not r.shed for r in reqs)
    return reqs


@pytest.fixture
def tracing():
    trace.enable()
    try:
        yield
    finally:
        trace.enable(False)


class _Recorder:
    """Stands in for ``jax.profiler.TraceAnnotation``: records names."""
    seen = []

    def __init__(self, name):
        self.name = name

    def __enter__(self):
        _Recorder.seen.append(self.name)

    def __exit__(self, *exc):
        return None


def test_off_builds_no_annotation(monkeypatch):
    def refuse(name):
        raise AssertionError(f"TraceAnnotation({name!r}) built while off")

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", refuse)
    assert trace.span("pd.a") is trace.span("pd.b")     # one shared no-op
    cfg, fe = _frontend()
    _wave(fe, cfg, seed=0, rid0=0)
    assert trace._stack == []


def test_on_enters_every_span_and_unwinds(monkeypatch, tracing):
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", _Recorder)
    _Recorder.seen = []
    cfg, fe = _frontend()
    _wave(fe, cfg, seed=1, rid0=0)
    assert SPANS <= set(_Recorder.seen), SPANS - set(_Recorder.seen)
    assert {n for n in _Recorder.seen if not n.startswith("pd.")} == set()
    assert trace._stack == []


def test_build_counter_charges_first_shape_to_its_span(tracing):
    f = jax.jit(lambda x: x * 2 + 1)
    x = jnp.ones((3, 17))
    total = trace.build_count()
    with trace.span("pd.test.first"):
        f(x).block_until_ready()
    with trace.span("pd.test.repeat"):
        f(x).block_until_ready()
    got = trace.builds()
    assert got["pd.test.first"] == 1
    assert "pd.test.repeat" not in got
    assert trace.build_count() == total + 1
    # the serving path: a second wave of the same shapes builds nothing
    cfg, fe = _frontend()
    _wave(fe, cfg, seed=2, rid0=0)
    before, total = trace.builds(), trace.build_count()
    _wave(fe, cfg, seed=3, rid0=10)
    assert trace.builds() == before
    assert trace.build_count() == total


def test_build_total_counts_while_off():
    f = jax.jit(lambda x: x - 3)
    x = jnp.ones((5, 11))
    total, charged = trace.build_count(), trace.builds()
    f(x).block_until_ready()
    assert trace.build_count() == total + 1
    assert trace.builds() == charged              # no attribution


@pytest.mark.parametrize("overlap", [True, False])
def test_wall_stamps_first_token_before_admit(overlap):
    cfg, params = reduced_params("granite-3-8b")
    fe = ClusterFrontend(cfg, topology={"default": (1, 1)}, params=params,
                         overlap_transfer=overlap)
    reqs = _wave(fe, cfg, seed=4, rid0=0)
    for r in reqs:
        assert 0.0 < r.wall_first_token <= r.wall_admit, r.rid
