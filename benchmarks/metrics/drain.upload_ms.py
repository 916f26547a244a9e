"""Host milliseconds a lap spends building its fresh ``DrainSim`` (the
host arrays handed to the device), mean over the laps of the window;
warm-up's lap is not among them."""


def read(run):
    laps = run.spans.window_s("lap.upload")
    return 1e3 * sum(laps) / len(laps) if laps else None
