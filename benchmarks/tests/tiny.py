"""The harness at the smoke's --tiny geometry: BENCHMARK.json with every
``dfly65k`` name swapped for its ``tiny128`` twin (the same drivers,
generator, reference and comparison; a 128-host dragonfly, 600 flows),
and the look for a chip stubbed HERE, not by an option of the program."""

import copy
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


def execute(workload, seed=2**31 + 17, seconds=0.3, trace=False):
    return harness.execute(workload, seed, seconds, trace,
                           time.perf_counter())
