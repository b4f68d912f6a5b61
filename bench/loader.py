"""The benchmark's parts that are found by name, each loaded by file
path: a metric's reader in ``metrics/<name>.py`` and a detector in
``detectors/<arch>.py`` (``detectors/tiny.py`` describes what a detector
module provides)."""
from __future__ import annotations

import functools
import importlib.util
import os

HERE = os.path.dirname(os.path.abspath(__file__))
DETECTORS = os.path.join(HERE, "detectors")


def _load(path: str, prefix: str, name: str):
    spec = importlib.util.spec_from_file_location(
        prefix + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    return _load(os.path.join(HERE, "metrics", name + ".py"),
                 "bench_metric_", name).read


def detector(arch: str):
    """The module ``<DETECTORS>/<arch>.py``."""
    return _detector_at(os.path.join(DETECTORS, arch + ".py"), arch)


@functools.cache
def _detector_at(path: str, arch: str):
    # loaded once a process, so that its jitted functions keep what they
    # compiled from one packet to the next
    return _load(path, "bench_detector_", arch)
