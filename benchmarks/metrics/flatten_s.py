"""Host seconds of the platform -> route -> flatten front end: the
benchmark's span around engine build, flow posting, latency advances
and ``flatten`` / ``capture_plan_snapshot`` during set-up."""


def read(run):
    return run.spans.total_s("flatten") or None
