"""The harness at the smoke's --tiny geometry: BENCHMARK.json with every
``dfly65k`` name swapped for its ``tiny128`` twin (the same drivers,
generator, reference and comparison; a 128-host dragonfly, 600 flows),
and the look for a chip stubbed HERE, not by an option of the program."""

import copy
import lzma
import os
import time

import jax

from lib import harness, manifest as mf, peaks


def tiny_manifest():
    m = copy.deepcopy(mf.load_manifest())

    def swap(s):
        return s.replace("dfly65k", "tiny128")

    for c in m["configs"]:
        c["name"], c["file"] = swap(c["name"]), swap(c["file"])
    for w in m["workloads"]:
        w["name"], w["config"] = swap(w["name"]), swap(w["config"])
    for metric in m["end_to_end"] + m["per_layer"]:
        if "workloads" in metric:
            metric["workloads"] = [swap(x) for x in metric["workloads"]]
    return m


def patch(monkeypatch):
    manifest = tiny_manifest()
    monkeypatch.setattr(mf, "load_manifest", lambda root=None: manifest)
    monkeypatch.setattr(harness, "find_devices",
                        lambda chips: jax.devices()[:chips])
    monkeypatch.setitem(peaks.PEAKS, "cpu", peaks.PEAKS["TPU v5 lite"])


def recorded(name, folder):
    """A trace recorded on the v5e, one window of
    ``tiny128-random.drain``, unpacked into ``folder``: ``tiny_drain``
    from before the program named anything (PR 26), or
    ``tiny_drain_scoped``, with the ``sg.*`` op-name paths, the ``sg:``
    spans and every ``bench:`` span of a lap (``tools/passes.py
    --keep``, PR 27)."""
    os.makedirs(folder, exist_ok=True)
    path = os.path.join(str(folder), name + ".xplane.pb")
    with lzma.open(os.path.join(os.path.dirname(__file__), "fixtures",
                                name + ".xplane.pb.xz")) as f, \
            open(path, "wb") as out:
        out.write(f.read())
    return path


def traced(monkeypatch, name="tiny_drain_scoped"):
    """``--trace 1`` on the CPU: the profiler writes nothing here, so
    stopping it leaves the recorded trace where the harness looks for
    the window's; the harness's own reduction reads it."""
    started = []
    profiler = harness.jax_profiler()
    monkeypatch.setattr(profiler, "start_trace",
                        lambda trace_dir, *a, **k: started.append(trace_dir))
    monkeypatch.setattr(profiler, "stop_trace", lambda: recorded(
        name, os.path.join(started[-1], "plugins", "profile", "recorded")))


def execute(workload, seed=2**31 + 17, seconds=0.3, trace=False):
    return harness.execute(workload, seed, seconds, trace,
                           time.perf_counter())
