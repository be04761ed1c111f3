"""Seeded metric-registry drift for the ``metrics`` pass
(tools/analyze/metriccheck.py) — every rule must fire on this file:

- ``fixture.documented_only`` is documented below but never emitted
  (``metric-unused``);
- ``fixture.never_documented`` is emitted but absent from the registry
  block (``metric-undocumented``);
- ``hist.fixture_latency`` is documented as a histogram but emitted via
  ``inc`` (``metric-kind-mismatch``);
- ``fleet.fixture_sources`` is a fleet-view gauge (``fleet.*`` names are
  gauge-kind, ISSUE 7) but emitted via ``inc``
  (``metric-kind-mismatch``);
- ``fed.peer_state.fixture`` is a membership gauge (the
  ``fed.peer_state`` family is gauge-kind, ISSUE 12) but emitted via
  ``inc`` (``metric-kind-mismatch``);
- ``gw.conns_live`` is the ingress live-conn gauge (the one gauge-kind
  name under ``gw.*``, ISSUE 15) but emitted via ``inc``
  (``metric-kind-mismatch``);
- ``ingress.fixture_events`` is documented below but never emitted
  (``metric-unused`` — pins the new ``ingress.*`` counter family in the
  registry cross-check);
- ``sweep.fixture_refills`` is documented below but never emitted
  (``metric-unused`` — pins the ``sweep.*`` counter family, which
  stays inc-kind, in the registry cross-check);
- ``autoscale.target_workers`` is the capacity plane's fleet-size gauge
  (the one gauge-kind name under ``autoscale.*``, ISSUE 18) but emitted
  via ``inc`` (``metric-kind-mismatch``);
- ``fed.conns_live`` is the federation transport's shared-loop conn
  gauge (ISSUE 18) but emitted via ``inc`` (``metric-kind-mismatch``);
- ``autoscale.fixture_actions`` is documented below but never emitted
  (``metric-unused`` — pins the ``autoscale.*`` action-counter family,
  which stays inc-kind, in the registry cross-check);
- ``sanitize.fixture_trips`` is documented below but never emitted
  (``metric-unused`` — pins the ``sanitize.*`` sanitizer-trip counter
  family (ISSUE 19: ``sanitize.loop_blocked``,
  ``sanitize.threads_leaked``), which stays inc-kind, in the registry
  cross-check);
- the computed-name ``inc`` cannot be registry-checked at all
  (``metric-dynamic-name``).
"""


class Metrics:  # stand-in so the fixture never imports the real package
    def inc(self, name, n=1):
        pass

    def observe(self, name, value):
        pass

    def set_gauge(self, name, value):
        pass


#: The fixture's registry block (same format as utils/metrics.py: the
#: contiguous ``#:`` lines directly above the METRICS assignment).
#:   fixture.documented_only   documented here, emitted nowhere
#:   hist.fixture_latency      a histogram name (observe-only kind)
#:   fleet.fixture_sources     a fleet-view gauge (set_gauge-only kind)
#:   fed.peer_state.fixture    a membership gauge (set_gauge-only kind)
#:   gw.conns_live             the ingress live-conn gauge (set_gauge-only kind)
#:   ingress.fixture_events    an ingress counter, documented but never emitted
#:   sweep.fixture_refills     a sweep counter, documented but never emitted
#:   autoscale.target_workers  the capacity plane's fleet-size gauge (set_gauge-only kind)
#:   fed.conns_live            the federation shared-loop conn gauge (set_gauge-only kind)
#:   autoscale.fixture_actions an autoscale action counter, documented but never emitted
#:   sanitize.fixture_trips    a sanitizer trip counter, documented but never emitted
METRICS = Metrics()


def provoke_metric_drift(suffix: str) -> None:
    METRICS.inc("fixture.never_documented")  # undocumented counter
    METRICS.inc("hist.fixture_latency")  # wrong emitter for a hist.* name
    METRICS.inc("fleet.fixture_sources")  # wrong emitter for a fleet.* gauge
    METRICS.inc("fed.peer_state.fixture")  # wrong emitter for a membership gauge
    METRICS.inc("gw.conns_live")  # wrong emitter for the ingress conn gauge
    METRICS.inc("autoscale.target_workers")  # wrong emitter for the fleet-size gauge
    METRICS.inc("fed.conns_live")  # wrong emitter for the fed conn gauge
    METRICS.inc("fixture." + suffix)  # dynamic name: unverifiable
