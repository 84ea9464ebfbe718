"""Known-good fixture for metric-name-catalog: every recorded name has a
row and the `metric.stale` row has a record site here, so the fixture doc
is fully reconciled."""
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


def stale_is_actually_recorded_here():
    telemetry.observe("metric.stale", 1.0)
