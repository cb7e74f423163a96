"""Host spans on the device trace's clock: under ``jax.profiler``, a
``Tracer(profiler=True)`` puts one host event of each span's name into the
profile, in the order the spans began, lasting as long as the span."""

import jax
from jax.profiler import ProfileData, ProfileOptions

from repro.core.fleet import Fleet
from repro.obs import Tracer
from repro.stream import StreamEngine, poisson_churn_trace


def _run(tracer=None):
    trace = poisson_churn_trace(num_sessions=4, arrival_rate=1.0, seed=3,
                                m_min=2, m_max=6, session_scale=10.0)
    return StreamEngine(Fleet.partition_pod(32, 2), "mdmt", seed=0,
                        tracer=tracer).run(trace)


def test_every_span_is_one_host_event_of_the_profile(tmp_path):
    _run()                      # compile outside the profile
    tr = Tracer(enabled=True, profiler=True)
    opts = ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        _run(tr)
    finally:
        jax.profiler.stop_trace()
    spans = sorted(tr.records(), key=lambda r: r["t0"])
    names = {r["name"] for r in spans}
    assert {"event", "gp_fold", "gp_flush", "posterior_upload", "admit",
            "mirrors", "retire"} <= names
    (pb,) = tmp_path.rglob("*.xplane.pb")
    host = sorted((e.start_ns, e.duration_ns, e.name)
                  for plane in ProfileData.from_file(str(pb)).planes
                  if plane.name.startswith("/host:")
                  for line in plane.lines for e in line.events
                  if e.name in names)
    assert [n for _, _, n in host] == [r["name"] for r in spans]
    for (_, dur_ns, name), r in zip(host, spans):
        assert abs(dur_ns / 1e3 - r["dur_us"]) <= 0.1 * r["dur_us"] + 50, \
            (name, dur_ns / 1e3, r["dur_us"])
