"""Shared test fixtures.

NOTE: do NOT set XLA_FLAGS / device-count overrides here — smoke tests and
benches must see the real single CPU device.  Only launch/dryrun.py (its own
process) forces 512 placeholder devices.
"""
import jax
import pytest


@pytest.fixture(scope="session")
def rng():
    return jax.random.PRNGKey(0)


@pytest.fixture
def no_retrace():
    """The no_retrace() context manager from repro.analysis.retrace.

    ``with no_retrace(eng._cont_step, eng._admit): ...`` asserts that
    the block grows no jit cache and moves no dispatch counter — the
    shared trace-once assertion for scheduler/per-request/plane-stream
    tests (QSQ002/QSQ003 argue the same thing statically).
    """
    from repro.analysis.retrace import no_retrace as _no_retrace

    return _no_retrace


@pytest.fixture
def host_spans(tmp_path):
    """A context manager that runs its block under a profiler session and
    then holds the block's ``serve.*`` host spans.

    ``with host_spans() as spans: ...`` leaves ``spans`` a list of
    ``(name, start_ns, end_ns, stats)`` in start order, ``stats`` the
    span's keyword arguments.  Device and ``TraceAnnotation`` events only:
    the Python tracer is off, as in the chip benchmark's traced runs.
    """
    import contextlib
    import glob
    import tempfile

    @contextlib.contextmanager
    def record():
        out = tempfile.mkdtemp(dir=tmp_path)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        spans: list = []
        jax.profiler.start_trace(out, profiler_options=opts)
        try:
            yield spans
        finally:
            jax.profiler.stop_trace()
        (path,) = glob.glob(f"{out}/**/*.xplane.pb", recursive=True)
        for plane in jax.profiler.ProfileData.from_file(path).planes:
            if plane.name.startswith("/host:"):
                spans += [(e.name, e.start_ns, e.end_ns, dict(e.stats))
                          for line in plane.lines for e in line.events
                          if e.name.startswith("serve.")]
        spans.sort(key=lambda s: (s[1], -s[2]))

    return record
