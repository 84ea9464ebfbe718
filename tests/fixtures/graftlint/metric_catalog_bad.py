"""Known-bad fixture for metric-name-catalog (vs metric_doc_fixture.md):
records two names with no catalog row; `metric.stale` is documented but
never recorded."""
from mxtpu import telemetry


def documented(i):
    telemetry.inc("good.counter")
    with telemetry.span("good.span", d2h=True):
        pass
    telemetry.gauge("family.a", 1)
    telemetry.observe("family.b", 0.5)
    telemetry.inc("dyn.r%d" % i)
    telemetry.inc("tagged.thing", tag="why")
    telemetry.record_retrace("fixture_site")
    telemetry.record_interval("good.interval", 0)


def undocumented():
    telemetry.inc("metric.undocumented")
    with telemetry.span("span.undocumented"):
        pass
