"""``BENCHMARK.json``: loading, the contract's checks, and finding each
configuration, traffic mix and per-layer metric reader by its name.

Everything that belongs to one configuration, mix or metric is a file
of its own, so a later change adds a cell by adding files and entries:

* ``configs[i]["file"]``: the configuration (JSON), whose ``kind``
  names its deployment, ``benchmarks/deployments/<kind>.py``: a
  ``Deployment(config, ctx, seed)`` class (the data on the device);
* ``benchmarks/traffic/<traffic>.json``: the mix's parameters, whose
  ``driver`` names its window driver, ``benchmarks/drivers/<driver>.py``:
  a ``Driver(spec, deployment, ctx, seed)`` class (``harness/traffic.py``);
* ``benchmarks/metrics/<metric>.py``: a ``read(reading)`` function.
"""
from __future__ import annotations

import importlib.util
import json
import os
import re
from typing import Dict, List

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_./-]{1,200}$")
E2E_SOURCES = {"host_clock", "device_trace"}
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
TOP_KEYS = {"command", "paths", "run_seconds", "configs", "workloads",
            "end_to_end", "per_layer"}
BENCH_DIR = "benchmarks"


def load(root: str) -> Dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def _line(s) -> bool:
    return (isinstance(s, str) and 1 <= len(s) <= 200 and "\n" not in s
            and "\t" not in s)


def _under(path: str, paths) -> bool:
    return any(path == p or path.startswith(p.rstrip("/") + "/")
               for p in paths)


def validate(m: Dict, root: str) -> List[str]:
    """Every breach of the manifest contract found in ``m`` (empty when
    it holds)."""
    err: List[str] = []
    if set(m) != TOP_KEYS:
        err.append(f"top-level keys {sorted(m)} != {sorted(TOP_KEYS)}")
        return err
    paths = m["paths"]
    if not (1 <= len(paths) <= 16) or not all(
            PATH.match(p) and ".." not in p.split("/") and
            not p.startswith("/") for p in paths):
        err.append(f"bad paths {paths}")
    cmd = m["command"]
    if not (1 <= len(cmd) <= 32) or not all(_line(w) for w in cmd):
        err.append("bad command")
    for w in cmd[1:]:
        if ("/" in w or w.endswith(".py")) and not _under(w, paths):
            err.append(f"command names {w} outside paths")
        if w.startswith("/") or ".." in w.split("/"):
            err.append(f"command path {w} leaves the repo")
    rs = m["run_seconds"]
    if not (isinstance(rs, int) and 1 <= rs <= 51):
        err.append(f"run_seconds {rs} not a whole number in 1..51")
    seen = set()

    def name(kind, n):
        if not (isinstance(n, str) and NAME.match(n)):
            err.append(f"{kind} name {n!r} breaks the name rule")
        if (kind, n) in seen:
            err.append(f"duplicate {kind} {n}")
        seen.add((kind, n))

    configs = {}
    for c in m["configs"]:
        if set(c) != {"name", "source", "file", "reduced", "why"}:
            err.append(f"config {c.get('name')} keys {sorted(c)}")
            continue
        name("config", c["name"])
        configs[c["name"]] = c
        if not (_line(c["source"]) and _line(c["why"])):
            err.append(f"config {c['name']}: source/why not one line")
        if not _under(c["file"], paths) or not os.path.exists(
                os.path.join(root, c["file"])):
            err.append(f"config {c['name']}: file {c['file']} missing "
                       "or outside paths")
        elif not os.path.exists(_path(root, "deployments", config(
                m, root, c["name"]).get("kind", ""), ".py")):
            err.append(f"config {c['name']}: no deployment file")
        if len(c["reduced"]) > 16 or not all(
                isinstance(k, str) and NAME.match(k) for k in c["reduced"]):
            err.append(f"config {c['name']}: bad reduced")
    if not 1 <= len(configs) <= 24:
        err.append("1 to 24 configs")
    if len({c["file"] for c in configs.values()}) != len(configs):
        err.append("two configs share a file")
    cells = {}
    pairs = set()
    for w in m["workloads"]:
        if set(w) != {"name", "config", "traffic", "chips", "why"}:
            err.append(f"workload {w.get('name')} keys {sorted(w)}")
            continue
        name("workload", w["name"])
        cells[w["name"]] = w
        if w["config"] not in configs:
            err.append(f"workload {w['name']}: unknown config")
        if not NAME.match(str(w["traffic"])):
            err.append(f"workload {w['name']}: traffic name")
        elif not os.path.exists(traffic_path(root, w["traffic"])):
            err.append(f"workload {w['name']}: no traffic file")
        elif not os.path.exists(_path(root, "drivers", traffic(
                root, w["traffic"]).get("driver", ""), ".py")):
            err.append(f"workload {w['name']}: no driver file")
        if (w["config"], w["traffic"]) in pairs:
            err.append(f"workload {w['name']}: config and traffic repeat")
        pairs.add((w["config"], w["traffic"]))
        if w["chips"] not in (1, 4):
            err.append(f"workload {w['name']}: chips {w['chips']}")
        if not _line(w["why"]):
            err.append(f"workload {w['name']}: why not one line")
    if not 1 <= len(cells) <= 24:
        err.append("1 to 24 workloads")
    four = sum(1 for w in cells.values() if w["chips"] == 4)
    if four > max(1, len(cells) // 2):
        err.append(f"{four} cells on four chips")
    used = {w["config"] for w in cells.values()}
    for c in configs:
        if c not in used:
            err.append(f"config {c} used by no cell")

    def metric(x, e2e: bool):
        keys = {"name", "unit", "better", "source"}
        keys |= {"bound"} if e2e else {"layer", "moves"}
        if not keys <= set(x) <= keys | {"workloads"}:
            err.append(f"metric {x.get('name')} keys {sorted(x)}")
            return
        name("metric", x["name"])
        if not UNIT.match(x["unit"]):
            err.append(f"metric {x['name']}: unit {x['unit']!r}")
        if x["better"] not in ("lower", "higher"):
            err.append(f"metric {x['name']}: better {x['better']!r}")
        if x["source"] not in (E2E_SOURCES if e2e else SOURCES):
            err.append(f"metric {x['name']}: source {x['source']}")
        for c in x.get("workloads", []):
            if c not in cells:
                err.append(f"metric {x['name']}: unknown workload {c}")
        if e2e and not (0.01 <= x["bound"] <= 0.25):
            err.append(f"metric {x['name']}: bound {x['bound']}")
        if not e2e and not _line(x["layer"]):
            err.append(f"metric {x['name']}: layer not one line")

    e2e = {x["name"]: x for x in m["end_to_end"]}
    for x in m["end_to_end"]:
        metric(x, True)
    for x in m["per_layer"]:
        metric(x, False)
        if x.get("moves") not in e2e:
            err.append(f"metric {x['name']}: moves {x.get('moves')}")
        elif not os.path.exists(metric_path(root, x["name"])):
            err.append(f"metric {x['name']}: no reader file")
    if not 1 <= len(e2e) <= 16 or "setup_s" not in e2e:
        err.append("1 to 16 end-to-end metrics, setup_s among them")
    if not 1 <= len(m["per_layer"]) <= 128:
        err.append("1 to 128 per-layer metrics")
    for c in cells:
        mine = [x["name"] for x in e2e_metrics(m, c)]
        if "setup_s" not in mine or len(mine) < 2:
            err.append(f"workload {c}: setup_s and one more end-to-end")
        if not per_layer_metrics(m, c):
            err.append(f"workload {c}: no per-layer metric")
    if len(json.dumps(m)) > 64 * 1024:
        err.append("manifest over 64 KiB")
    return err


def e2e_metrics(m: Dict, cell: str) -> List[Dict]:
    return [x for x in m["end_to_end"]
            if "workloads" not in x or cell in x["workloads"]]


def per_layer_metrics(m: Dict, cell: str) -> List[Dict]:
    moved = {x["name"] for x in e2e_metrics(m, cell)}
    return [x for x in m["per_layer"]
            if (cell in x["workloads"] if "workloads" in x
                else x["moves"] in moved)]


def workload(m: Dict, name: str) -> Dict:
    for w in m["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(m: Dict, root: str, name: str) -> Dict:
    for c in m["configs"]:
        if c["name"] == name:
            with open(os.path.join(root, c["file"])) as f:
                return json.load(f)
    raise KeyError(f"no config {name!r} in BENCHMARK.json")


def _path(root: str, kind: str, name: str, ext: str) -> str:
    if not NAME.match(name):
        raise KeyError(f"bad {kind} name {name!r}")
    return os.path.join(root, BENCH_DIR, kind, name + ext)


def traffic_path(root: str, name: str) -> str:
    return _path(root, "traffic", name, ".json")


def traffic(root: str, name: str) -> Dict:
    with open(traffic_path(root, name)) as f:
        return json.load(f)


def metric_path(root: str, name: str) -> str:
    return _path(root, "metrics", name, ".py")


def _module(root: str, kind: str, name: str):
    path = _path(root, kind, name, ".py")
    spec = importlib.util.spec_from_file_location(
        f"bench_{kind}_" + re.sub(r"\W", "_", name), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(root: str, name: str):
    """The ``read`` function of ``benchmarks/metrics/<name>.py``."""
    return _module(root, "metrics", name).read


def deployment(root: str, config: Dict):
    """The ``Deployment`` class of the configuration's ``kind``."""
    return _module(root, "deployments", config["kind"]).Deployment


def driver(root: str, spec: Dict):
    """The ``Driver`` class that the traffic mix's ``driver`` names."""
    return _module(root, "drivers", spec["driver"]).Driver
