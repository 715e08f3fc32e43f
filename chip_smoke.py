#!/usr/bin/env python
"""chip_smoke.py — the quickest proof that shifu-tpu still starts on the chip.

Drives the system's main path once, through the entry point a user calls
(`bin/shifu`, ONE CHILD PROCESS PER STEP), on seeded data this script
writes itself:

    new, init, stats (maxNumBin 32), norm, varsel,
    train + eval -run with GBT (TreeNum 5, MaxDepth 6 — the `gbt` bench
    width: fused-kernel levels L = 1..32), then with NN (NumHiddenNodes
    [50] — the `small` bench width),
    serve --port 0 on the trained set: /healthz, three POST /score
    requests of 1, 16 and 256 records, SIGTERM, exit code 0.

This parent never imports jax: the chip belongs to one process at a time
and every child takes it in turn. What device the run used is read from
the run manifests the children wrote (`.shifu/runs/*.json`, `jax`
section). Any child exit code != 0, any manifest whose backend is not
"tpu", a GBT forest not grown by the compiled Pallas kernel, an eval AUC
under the floor, or a wrong/non-finite served score is a hard failure:
the script exits non-zero and prints NO result line.

    python chip_smoke.py                 one chip; the driver's check
    python chip_smoke.py --chips 4       ONLY the data-parallel train/eval
                                         on the 4-chip mesh and its
                                         one-device comparison (no serve)
    python chip_smoke.py --rehearse --rows 4000
                                         walk every phase on whatever
                                         platform the children find (the
                                         CPU sandbox); always exits 1 and
                                         prints no result line

Earlier lines are one JSON object per phase (wall seconds, compile
seconds from the manifest's jax.compile timer); the LAST line on success
is exactly {"ok": true, "device": {"platform", "kind", "count"}}.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import shutil
import signal
import subprocess
import sys
import time
import urllib.request

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
SHIFU = os.path.join(HERE, "bin", "shifu")

N_NUMERIC, N_CAT, CAT_VALUES = 24, 6, 48
AUC_FLOOR = 0.80
GBT_PARAMS = {"TreeNum": 5, "MaxDepth": 6, "Impurity": "variance",
              "Loss": "squared", "LearningRate": 0.1,
              "FeatureSubsetStrategy": "ALL"}
NN_PARAMS = {"NumHiddenLayers": 1, "ActivationFunc": ["tanh"],
             "NumHiddenNodes": [50], "RegularizedConstant": 0.0,
             "LearningRate": 0.1, "Propagation": "R"}
NN_EPOCHS = 20
# libtpu's own switches for "this process sees one chip of the host"
ONE_TPU_CHIP_ENV = {"TPU_VISIBLE_CHIPS": "0", "TPU_VISIBLE_DEVICES": "0",
                    "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
                    "TPU_PROCESS_BOUNDS": "1,1,1"}


class SmokeFailure(Exception):
    pass


def say(**kv) -> None:
    print(json.dumps(kv, sort_keys=True), flush=True)


# ---------------------------------------------------------------------------
# seeded data
# ---------------------------------------------------------------------------


def write_dataset(data_dir: str, rows: int, seed: int):
    """rows x 30 pipe-delimited columns (24 numeric, 6 categorical of 48
    values) + a binary target that depends on both kinds; ~1 % of the
    tokens are the missing marker "?", so the numeric columns reach the
    coercing string parser. Returns (data_path, header_path, names)."""
    import pandas as pd

    rng = np.random.default_rng(seed)
    os.makedirs(data_dir, exist_ok=True)
    # the signal is drawn BEFORE the rows, so it is the same at any --rows;
    # it sits in a few columns so that five depth-6 trees can find it
    w = np.zeros(N_NUMERIC)
    w[[0, 3, 6, 9]] = [1.0, -0.8, 0.6, 0.5]
    cat_effect = np.zeros((N_CAT, CAT_VALUES))
    cat_effect[:2] = rng.normal(size=(2, CAT_VALUES)) * 0.7
    x = rng.normal(size=(rows, N_NUMERIC))
    cats = rng.integers(0, CAT_VALUES, size=(rows, N_CAT))
    z = x @ w + cat_effect[np.arange(N_CAT), cats].sum(axis=1)
    z = 2.5 * (z - z.mean()) / z.std()
    y = rng.random(rows) < 1.0 / (1.0 + np.exp(-z))
    names = (["target"] + [f"num_{j}" for j in range(N_NUMERIC)]
             + [f"cat_{j}" for j in range(N_CAT)])
    frame = {"target": np.where(y, "P", "N")}
    for j in range(N_NUMERIC):
        col = pd.Series(x[:, j]).map("{:.6g}".format)
        col[rng.random(rows) < 0.01] = "?"
        frame[f"num_{j}"] = col
    for j in range(N_CAT):
        col = pd.Series(cats[:, j]).map("c{:02d}".format)
        col[rng.random(rows) < 0.01] = "?"
        frame[f"cat_{j}"] = col
    data_path = os.path.join(data_dir, "data.txt")
    header_path = os.path.join(data_dir, "header.txt")
    pd.DataFrame(frame).to_csv(data_path, sep="|", header=False,
                               index=False)
    with open(header_path, "w") as fh:
        fh.write("|".join(names) + "\n")
    return data_path, header_path, names


# ---------------------------------------------------------------------------
# children + manifests
# ---------------------------------------------------------------------------


def child_env(extra=None) -> dict:
    env = dict(os.environ)
    env.update(extra or {})
    return env


def latest_manifest(root: str, step: str) -> dict:
    pat = re.compile(rf"^{re.escape(step)}-(\d+)\.json$")
    runs = os.path.join(root, ".shifu", "runs")
    paths = [os.path.join(runs, f) for f in sorted(
        os.listdir(runs) if os.path.isdir(runs) else []) if pat.match(f)]
    if not paths:
        raise SmokeFailure(f"step {step}: no run manifest under {root}")
    seq = lambda p: int(pat.match(os.path.basename(p)).group(1))  # noqa: E731
    with open(max(paths, key=seq)) as fh:
        return json.load(fh)


def manifest_facts(m: dict) -> dict:
    """The few manifest fields the smoke reports and checks."""
    metrics = m.get("metrics") or {}
    comp = (metrics.get("timers") or {}).get("jax.compile")
    if comp is not None:  # every backend compile of the step (obs.jaxprobe)
        seconds = comp.get("seconds", 0.0)
    else:  # serve keeps no jax probe: lower+compile at its profiled seam
        seconds = ((m.get("profile") or {}).get("totals") or {}).get(
            "compileSeconds", 0.0)
    return {"jax": m.get("jax") or {},
            "compile_seconds": round(float(seconds or 0.0), 3),
            "compiles": int((metrics.get("counters") or {}).get(
                "jax.compiles", 0) or 0)}


def device_of(jax_info: dict) -> tuple:
    """(platform, kind, count) as the child that wrote the manifest found."""
    return (jax_info.get("backend"), jax_info.get("deviceKind"),
            jax_info.get("deviceCount"))


class Run:
    """One smoke run: the work dir, the platform gate, the phase log."""

    def __init__(self, work: str, rehearse: bool):
        self.work = work
        self.rehearse = rehearse
        self.device = None  # (platform, kind, count) as a child found it
        self.phases = []

    def gate(self, phase: str, jax_info: dict, want_count=None) -> None:
        """Every manifest that touched a device must say tpu (and the
        expected device count) — checked as soon as the step ends, so a
        sandbox without a chip stops after `init`, not after training."""
        backend = jax_info.get("backend")
        count = jax_info.get("deviceCount")
        if not self.rehearse and backend != "tpu":
            raise SmokeFailure(
                f"{phase}: manifest says backend={backend!r}, not 'tpu' — "
                "this smoke only passes on the chip")
        if want_count is not None and count != want_count:
            raise SmokeFailure(
                f"{phase}: manifest says deviceCount={count}, "
                f"expected {want_count}")

    def step(self, root: str, phase: str, args, *, step=None, env=None,
             want_count=None) -> dict:
        """bin/shifu <args> as a child of its own, cwd = the model set."""
        t0 = time.time()
        proc = subprocess.run([sys.executable, SHIFU] + list(args), cwd=root,
                              env=child_env(env), stdout=subprocess.PIPE,
                              stderr=subprocess.STDOUT, text=True)
        wall = time.time() - t0
        if proc.returncode != 0:
            sys.stdout.write(proc.stdout[-6000:] + "\n")
            raise SmokeFailure(f"{phase}: `shifu {' '.join(args)}` exited "
                               f"{proc.returncode}")
        manifest = latest_manifest(root, step) if step else {}  # `new`: none
        facts = manifest_facts(manifest)
        if step:
            self.gate(phase, facts["jax"], want_count)
            self.device = device_of(facts["jax"])
        rec = {"phase": phase, "wall_seconds": round(wall, 2),
               "compile_seconds": facts["compile_seconds"],
               "compiles": facts["compiles"],
               "backend": facts["jax"].get("backend"),
               "devices": facts["jax"].get("deviceCount")}
        self.phases.append(rec)
        say(**rec)
        return manifest


def edit_config(root: str, fn) -> None:
    path = os.path.join(root, "ModelConfig.json")
    with open(path) as fh:
        cfg = json.load(fh)
    fn(cfg)
    with open(path, "w") as fh:
        json.dump(cfg, fh, indent=2)


def prepare(run: Run, rows: int, seed: int, want_count=None, env=None) -> str:
    """data + new/init/stats/norm/varsel; returns the model-set dir."""
    t0 = time.time()
    data_path, header_path, _ = write_dataset(
        os.path.join(run.work, "data"), rows, seed)
    say(phase="data", rows=rows, columns=N_NUMERIC + N_CAT, seed=seed,
        wall_seconds=round(time.time() - t0, 2),
        bytes=os.path.getsize(data_path))
    run.step(run.work, "new", ["new", "Smoke", "-t", "GBT"], env=env)
    root = os.path.join(run.work, "Smoke")

    def point_at_data(cfg):
        ds = cfg["dataSet"]
        ds.update(dataPath=data_path, headerPath=header_path,
                  dataDelimiter="|", headerDelimiter="|",
                  targetColumnName="target", posTags=["P"], negTags=["N"])
        cfg["stats"]["maxNumBin"] = 32
        ev = cfg["evals"][0]["dataSet"]
        ev.update(dataPath=data_path, headerPath=header_path,
                  dataDelimiter="|", headerDelimiter="|")

    edit_config(root, point_at_data)
    for name in ("init", "stats", "norm", "varsel"):
        run.step(root, name, [name], step=name, env=env,
                 want_count=want_count)
    return root


def set_algorithm(root: str, alg: str) -> None:
    def fn(cfg):
        cfg["train"]["algorithm"] = alg
        cfg["train"]["params"] = dict(GBT_PARAMS if alg == "GBT"
                                      else NN_PARAMS)
        if alg == "NN":
            cfg["train"]["numTrainEpochs"] = NN_EPOCHS

    edit_config(root, fn)
    # a user switching algorithm sets the old models aside, else eval and
    # serve would average the two families
    models = os.path.join(root, "models")
    if os.path.isdir(models):
        shutil.move(models, os.path.join(root, f"models.before_{alg}"))


def eval_auc(root: str) -> float:
    with open(os.path.join(root, "evals", "Eval1",
                           "EvalPerformance.json")) as fh:
        return float(json.load(fh)["areaUnderRoc"])


def valid_error(root: str) -> float:
    with open(os.path.join(root, "tmp", "train", "val_error_0.txt")) as fh:
        return float(fh.read().split()[0])


def check_kernel(manifest: dict) -> dict:
    """The GBT forest must have been grown by the COMPILED Pallas kernel
    at every fused level — not the XLA lowering, not interpret mode."""
    prof = manifest.get("profile") or {}
    ann = prof.get("annotations") or {}
    kern = ann.get("ops.hist_pallas") or {}
    lowering = (ann.get("train.tree") or {}).get("pallasLowering")
    fused = (prof.get("programs") or {}).get("tree.pallas_fused") or {}
    facts = {"pallasLowering": lowering, "kernel": kern,
             "fusedDispatches": fused.get("dispatches", 0),
             "fusedCostSource": fused.get("costSource")}
    if lowering != "pallas":
        raise SmokeFailure(f"GBT train: checkpoint fingerprint lowering is "
                           f"{lowering!r}, not 'pallas'")
    if not kern or kern.get("interpret") or not kern.get("fusedScan"):
        raise SmokeFailure(f"GBT train: kernel annotation {kern!r} is not "
                           "the compiled fused kernel")
    if fused.get("dispatches", 0) < GBT_PARAMS["TreeNum"]:
        raise SmokeFailure("GBT train: tree.pallas_fused dispatched "
                           f"{fused.get('dispatches', 0)} times, expected "
                           f">= {GBT_PARAMS['TreeNum']}")
    return facts


def train_eval(run: Run, root: str, alg: str, tag: str, env=None,
               want_count=None) -> dict:
    set_algorithm(root, alg)
    m = run.step(root, f"{tag}train.{alg}", ["train"], step="train", env=env,
                 want_count=want_count)
    run.step(root, f"{tag}eval.{alg}", ["eval", "-run"], step="eval",
             env=env, want_count=want_count)
    out = {"manifest": m, "auc": eval_auc(root),
           "valid_error": valid_error(root)}
    say(phase=f"{tag}result.{alg}", auc=out["auc"],
        valid_error=out["valid_error"])
    if not math.isfinite(out["auc"]) or out["auc"] < AUC_FLOOR:
        raise SmokeFailure(f"{alg} eval AUC {out['auc']} is under the "
                           f"{AUC_FLOOR} floor")
    return out


# ---------------------------------------------------------------------------
# serve
# ---------------------------------------------------------------------------


def sample_records(data_path: str, header_path: str, n: int):
    with open(header_path) as fh:
        names = fh.read().strip().split("|")
    recs = []
    with open(data_path) as fh:
        for line in fh:
            fields = line.rstrip("\n").split("|")
            recs.append({k: v for k, v in zip(names[1:], fields[1:])})
            if len(recs) == n:
                break
    return recs


def http_json(url: str, body=None, timeout=600):
    data = None if body is None else json.dumps(body).encode()
    req = urllib.request.Request(
        url, data=data, headers={"Content-Type": "application/json"})
    with urllib.request.urlopen(req, timeout=timeout) as resp:
        return resp.status, json.loads(resp.read().decode())


def serve_phase(run: Run, root: str, data_dir: str) -> None:
    t0 = time.time()
    log_path = os.path.join(run.work, "serve.log")
    log = open(log_path, "w")
    proc = subprocess.Popen(
        [sys.executable, SHIFU, "serve", "--port", "0", "--warm", "1,16,256"],
        cwd=root, env=child_env(), stdout=subprocess.PIPE, stderr=log,
        text=True)
    try:
        line = proc.stdout.readline()  # "listening on host:port (...)"
        if not line.startswith("listening on "):
            raise SmokeFailure(f"serve: no listening line (got {line!r}; "
                               f"see {log_path})")
        base = "http://" + line.split()[2]
        deadline = time.time() + 300
        while True:
            try:
                status, health = http_json(base + "/healthz", timeout=10)
                if status == 200:
                    break
            except OSError:
                pass
            if time.time() > deadline or proc.poll() is not None:
                raise SmokeFailure("serve: /healthz never became ready")
            time.sleep(0.5)  # shifu: noqa[SH104] one local poller, no herd
        ready = time.time() - t0
        recs = sample_records(os.path.join(data_dir, "data.txt"),
                              os.path.join(data_dir, "header.txt"), 256)
        means = {}
        latency = {}
        for n in (1, 16, 256):
            t1 = time.time()
            status, out = http_json(base + "/score", {"records": recs[:n]})
            latency[n] = round(time.time() - t1, 4)
            scores = [s["mean"] for s in out.get("scores", [])]
            if status != 200 or len(scores) != n:
                raise SmokeFailure(f"serve: {n}-record request gave HTTP "
                                   f"{status} with {len(scores)} scores")
            if not all(math.isfinite(float(s)) for s in scores):
                raise SmokeFailure(f"serve: non-finite score in the "
                                   f"{n}-record request")
            means[n] = scores
        if means[1][0] != means[256][0] or means[16] != means[256][:16]:
            raise SmokeFailure(
                f"serve: the same record scored {means[1][0]} alone and "
                f"{means[256][0]} inside the 256-record request")
        proc.send_signal(signal.SIGTERM)
        rc = proc.wait(timeout=120)
        if rc != 0:
            raise SmokeFailure(f"serve: drain exited {rc} after SIGTERM")
    finally:
        if proc.poll() is None:
            proc.kill()
            proc.wait()
        log.close()
    facts = manifest_facts(latest_manifest(root, "serve"))
    run.gate("serve", facts["jax"], 1)
    rec = {"phase": "serve", "wall_seconds": round(time.time() - t0, 2),
           "ready_seconds": round(ready, 2),
           "compile_seconds": facts["compile_seconds"],
           "compiles": facts["compiles"], "request_seconds": latency,
           "fused": health.get("fused"), "score_first": means[1][0],
           "backend": facts["jax"].get("backend")}
    run.phases.append(rec)
    say(**rec)


# ---------------------------------------------------------------------------
# the two modes
# ---------------------------------------------------------------------------


def cache_report() -> dict:
    """Where the children kept compiled programs, and how many entries —
    by the rule the children themselves follow (no jax in this import)."""
    sys.path.insert(0, HERE)
    from shifu_tpu.utils.platform import CACHE_ENV, checkout_cache_dir

    outside = os.environ.get(CACHE_ENV)
    path = outside or checkout_cache_dir()
    entries = sum(1 for _ in os.scandir(path)) if os.path.isdir(path) else 0
    return {"phase": "compile_cache", "dir": path, "entries": entries,
            "placed_by": CACHE_ENV if outside else "place_compile_cache"}


def one_chip(run: Run, rows: int, seed: int) -> None:
    root = prepare(run, rows, seed, want_count=None if run.rehearse else 1)
    gbt = train_eval(run, root, "GBT", "")
    if run.rehearse and run.device[0] != "tpu":
        say(phase="kernel", skipped="rehearsal off the chip: the XLA "
            "lowering grew the forest")
    else:
        say(phase="kernel", **check_kernel(gbt["manifest"]))
    train_eval(run, root, "NN", "")
    serve_phase(run, root, os.path.join(run.work, "data"))


_ROOT_SPLIT = """
import json, sys
sys.path.insert(0, sys.argv[1])
from shifu_tpu.models.tree import TreeModelSpec
out = []
for p in sys.argv[2:]:
    t = TreeModelSpec.load(p).trees[0]
    out.append([int(t.feature[0]), [int(b) for b in t.left_mask[0]]])
print(json.dumps(out))
"""


def four_chips(run: Run, rows: int, seed: int) -> None:
    """The data-parallel path and what it is compared with, nothing else:
    preparation + NN/GBT train/eval on the 4-device mesh, the same two
    trainings in children that see ONE device, and the comparison."""
    multi_env, single_env = {}, dict(ONE_TPU_CHIP_ENV)
    if run.rehearse:  # virtual host devices stand in for the chips
        flag = "--xla_force_host_platform_device_count="
        multi_env = {"XLA_FLAGS": flag + "4"}
        single_env = {"XLA_FLAGS": flag + "1"}
    # preflight: does the restriction give a child exactly one device?
    # (15 s here, instead of finding out after the mesh trainings)
    probe = subprocess.run(
        [sys.executable, "-c", "import jax; print(len(jax.devices()))"],
        env=child_env(single_env), capture_output=True, text=True)
    seen = probe.stdout.strip().splitlines()[-1:] or ["?"]
    say(phase="one_device_preflight", devices=seen[0], env=single_env)
    if probe.returncode != 0 or seen[0] != "1":
        sys.stdout.write(probe.stderr[-3000:] + "\n")
        raise SmokeFailure(f"a child restricted with {single_env} saw "
                           f"{seen[0]} devices, not 1")
    root4 = prepare(run, rows, seed, want_count=4, env=multi_env)
    root1 = os.path.join(run.work, "Smoke_1dev")
    shutil.copytree(root4, root1)
    shutil.rmtree(os.path.join(root1, ".shifu"), ignore_errors=True)
    res = {}
    for alg in ("GBT", "NN"):
        res[alg, 4] = train_eval(run, root4, alg, "mesh4.", env=multi_env,
                                 want_count=4)
        res[alg, 1] = train_eval(run, root1, alg, "one.", env=single_env,
                                 want_count=1)
    for alg in ("GBT", "NN"):
        a, b = res[alg, 4], res[alg, 1]
        d_auc = abs(a["auc"] - b["auc"])
        d_err = abs(a["valid_error"] - b["valid_error"])
        gauges = ((a["manifest"].get("metrics") or {}).get("gauges") or {})
        placed = {k: gauges.get(k) for k in (
            "mesh.rows_per_device.min", "mesh.rows_per_device.max",
            "mesh.row_devices", "mesh.devices")}
        say(phase=f"compare.{alg}", auc_mesh4=a["auc"], auc_one=b["auc"],
            valid_error_mesh4=a["valid_error"],
            valid_error_one=b["valid_error"], placement=placed)
        if d_auc > 1e-3 or d_err > 1e-3:
            raise SmokeFailure(f"{alg}: 4-device vs 1-device differ by "
                               f"AUC {d_auc:.2e}, valid error {d_err:.2e}")
        lo, hi = placed["mesh.rows_per_device.min"], placed[
            "mesh.rows_per_device.max"]
        if placed["mesh.row_devices"] != 4 or not lo or lo != hi:
            raise SmokeFailure(f"{alg}: rows are not spread evenly over 4 "
                               f"devices: {placed}")
    # the result line's device is the mesh's, not the last child's
    run.device = device_of(res["NN", 4]["manifest"]["jax"])
    proc = subprocess.run(
        [sys.executable, "-c", _ROOT_SPLIT, HERE,
         os.path.join(root4, "models.before_NN", "model0.gbt"),
         os.path.join(root1, "models.before_NN", "model0.gbt")],
        env=child_env({"JAX_PLATFORMS": "cpu"}), capture_output=True,
        text=True)
    if proc.returncode != 0:
        raise SmokeFailure("root-split reader failed: " + proc.stderr[-2000:])
    split4, split1 = json.loads(proc.stdout.strip().splitlines()[-1])
    say(phase="compare.root_split", feature_mesh4=split4[0],
        feature_one=split1[0], identical=split4 == split1)
    if split4 != split1:
        raise SmokeFailure("the first GBT tree's root split differs between "
                           "the 4-device and the 1-device run")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--rows", type=int, default=200_000)
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    ap.add_argument("--work", default=os.path.join(HERE, "chip_smoke_work"),
                    help="scratch model-set dir (git-ignored, recreated)")
    ap.add_argument("--rehearse", action="store_true",
                    help="walk the phases on any platform; never passes")
    args = ap.parse_args()
    if not os.path.isfile(SHIFU):
        print(f"chip_smoke: {SHIFU} is missing — this script drives the "
              "repo's own entry point and is nothing without it",
              file=sys.stderr)
        return 2
    shutil.rmtree(args.work, ignore_errors=True)
    os.makedirs(args.work)
    run = Run(args.work, args.rehearse)
    t0 = time.time()
    try:
        (four_chips if args.chips == 4 else one_chip)(run, args.rows,
                                                      args.seed)
    except SmokeFailure as e:
        serve_log = os.path.join(args.work, "serve.log")
        if os.path.isfile(serve_log):
            with open(serve_log) as fh:
                sys.stdout.write(fh.read()[-6000:] + "\n")
        say(phase="FAILED", error=str(e))
        return 1
    cache = cache_report()
    say(**cache)
    say(phase="total", wall_seconds=round(time.time() - t0, 2),
        compile_seconds=round(sum(p["compile_seconds"]
                                  for p in run.phases), 2),
        rows=args.rows)
    if cache["entries"] == 0:
        say(phase="FAILED", error="the compile cache holds no entry")
        return 1
    platform, kind, count = run.device
    if args.rehearse or platform != "tpu" or count != args.chips:
        say(phase="FAILED", error=f"rehearsal or wrong device "
            f"({platform}, {kind}, {count}): no result line")
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": count}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
