#!/usr/bin/env python3
"""One run of one benchmark cell, measured from the client's side.

    python benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

A cell (``BENCHMARK.json`` ``workloads[]``) is one configuration
(``benchmark/configs/<config>.json``: a deployment, its sizes, its server
flags) under one traffic mix (``benchmark/traffic/<traffic>.json``: loop,
connections, rate, request shape, and the generator module that makes it).
This process is the jax-free parent: it starts ONE server child
(``launch_server.py``, the only holder of the chip), builds the plain
reference while that boots, fills the configuration's data over the wire,
has the load workers warm every request shape of the mix, measures for
``--seconds``, checks every reply against the reference, stops the child
with SIGTERM (exit 0 within a bound is part of ``correct``) and prints, as
its last line, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``.

``--trace 0`` prints the cell's end-to-end metrics.  ``--trace 1`` arms the
server's stage spans for the window and profiles the device for a slice in
its middle, and prints the cell's per-layer metrics instead
(``benchmark/layer_metrics/<name>.py``, one reader each).

No TPU, or fewer chips than the cell asks for: non-zero exit, no result
line.  ``--rehearse-cpu`` is the only way onto the CPU — tiny sizes,
``"correct": false`` — a rehearsal of the script, never a measurement.
"""
import time

_T0 = time.monotonic()  # set-up is counted from here

import argparse  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import select  # noqa: E402
import shutil  # noqa: E402
import signal  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

import numpy as np  # noqa: E402

READY_TIMEOUT_S = 300.0   # server boot: jax import, backend init, g++
SIGTERM_BOUND_S = 60.0    # a told-to-stop server must exit 0 within this
TRACE_RING = 20000        # frames the span ring holds in a traced run
SLICE_SHARE, SLICE_MAX_S = 0.3, 3.0  # the profiled slice of the window
MUST_BE_ZERO = ("host_colocations", "merge_fallbacks", "lane_faults",
                "lanes_quarantined")


class BenchFailure(Exception):
    """The run cannot give a result: non-zero exit, no result line."""


def log(msg: str) -> None:
    print(f"[bench +{time.monotonic() - _T0:6.1f}s] {msg}", file=sys.stderr, flush=True)


# -- the manifest ---------------------------------------------------------------


def _json(path: str) -> dict:
    with open(path) as fh:
        return json.load(fh)


def _reported_in(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


def load_cell(root: str, name: str, rehearse: bool, overrides=()) -> dict:
    """Everything one cell is made of, found by the names in the manifest."""
    manifest = _json(os.path.join(root, "BENCHMARK.json"))
    cells = {w["name"]: w for w in manifest["workloads"]}
    if name not in cells:
        raise BenchFailure(f"no workload {name!r} in BENCHMARK.json "
                           f"(there are: {', '.join(cells)})")
    cell = cells[name]
    entry = next(c for c in manifest["configs"] if c["name"] == cell["config"])
    config = _json(os.path.join(root, entry["file"]))
    params = _json(os.path.join(root, manifest["paths"][0], "traffic",
                                cell["traffic"] + ".json"))
    sizes = dict(config["sizes"])
    if rehearse:
        sizes.update(config.get("rehearse", {}))
        params.update(params.get("rehearse", {}))
    for item in overrides:
        key, _, value = item.partition("=")
        params[key] = json.loads(value)
    return {
        "name": name, "chips": cell["chips"], "config": config, "sizes": sizes,
        "params": params,
        "end_to_end": [m for m in manifest["end_to_end"] if _reported_in(m, name)],
        "per_layer": [m for m in manifest["per_layer"] if _reported_in(m, name)],
    }


def load_reader(name: str):
    """The ``read(obs)`` of one per-layer metric, found by the metric's name."""
    path = os.path.join(HERE, "layer_metrics", name + ".py")
    spec = importlib.util.spec_from_file_location("layer_metric_" + name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- the server child -------------------------------------------------------------


class Server:
    """The one process that holds the chip."""

    def __init__(self, cell: dict, rehearse: bool, log_path: str):
        ready_r, ready_w = os.pipe()
        ctl_r, self._ctl_w = os.pipe()
        self._ans_r, ans_w = os.pipe()
        flags = ["--port", "0", "--ready-fd", str(ready_w), *cell["config"]["server_flags"]]
        env = dict(os.environ)
        env["PYTHONPATH"] = ROOT + (os.pathsep + env["PYTHONPATH"]
                                    if env.get("PYTHONPATH") else "")
        if rehearse:
            flags += ["--platform", "cpu"]
            env["JAX_PLATFORMS"] = "cpu"
            if cell["chips"] > 1:
                env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") + " --xla_force_host_"
                                    f"platform_device_count={cell['chips']}").strip()
        cmd = [sys.executable, os.path.join(HERE, "launch_server.py"),
               "--control-fd", str(ctl_r), "--answer-fd", str(ans_w), "--", *flags]
        self._t0 = time.monotonic()
        with open(log_path, "wb") as logf:
            self.proc = subprocess.Popen(
                cmd, stdout=logf, stderr=subprocess.STDOUT, env=env, cwd=ROOT,
                pass_fds=(ready_w, ctl_r, ans_w))
        for fd in (ready_w, ctl_r, ans_w):
            os.close(fd)
        self._ready_r = ready_r
        self._answers = os.fdopen(self._ans_r, "r")
        self.log_path = log_path

    def _died(self, what: str) -> BenchFailure:
        with open(self.log_path, "rb") as fh:
            tail = fh.read()[-2000:].decode(errors="replace")
        return BenchFailure(f"server {what} (rc={self.proc.poll()}):\n{tail}")

    def wait_ready(self) -> str:
        buf = b""
        try:
            while b"\n" not in buf:
                left = READY_TIMEOUT_S - (time.monotonic() - self._t0)
                if left <= 0:
                    raise self._died(f"gave no READY line in {READY_TIMEOUT_S:.0f}s")
                if select.select([self._ready_r], [], [], min(left, 0.5))[0]:
                    chunk = os.read(self._ready_r, 4096)
                    if not chunk:
                        raise self._died("exited before READY")
                    buf += chunk
                elif self.proc.poll() is not None:
                    raise self._died("died before READY")
        finally:
            os.close(self._ready_r)
        _ready, host, port, _pid = buf.split(b"\n", 1)[0].decode().split()
        self.boot_s = time.monotonic() - self._t0
        return f"tpu://{host}:{port}"

    def control(self, line: str, timeout: float = 120.0) -> dict:
        os.write(self._ctl_w, (line + "\n").encode())
        if not select.select([self._answers], [], [], timeout)[0]:
            raise BenchFailure(f"the launcher did not answer {line.split()[0]!r} "
                               f"in {timeout:.0f}s")
        answer = json.loads(self._answers.readline())
        if not answer.get("ok"):
            raise BenchFailure(f"{line.split()[0]}: {answer.get('error')}")
        return answer

    def stop(self):
        """SIGTERM; (seconds it took, exit code or None if it had to be killed)."""
        t0 = time.monotonic()
        self.proc.send_signal(signal.SIGTERM)
        try:
            rc = self.proc.wait(timeout=SIGTERM_BOUND_S)
        except subprocess.TimeoutExpired:
            rc = None
        self.close()
        return time.monotonic() - t0, rc

    def close(self) -> None:
        if self.proc.poll() is None:
            self.proc.kill()
        self.proc.wait(timeout=30)
        if self._ctl_w >= 0:
            os.close(self._ctl_w)
            self._ctl_w = -1
            self._answers.close()


# -- what the server says of itself ------------------------------------------------


def parse_info(text: str) -> dict:
    out = {}
    for line in text.splitlines():
        if line and not line.startswith("#") and ":" in line:
            k, v = line.split(":", 1)
            out[k] = v
    return out


def snapshot(client) -> dict:
    """INFO and METRICS, as an operator reads them."""
    info = parse_info(client.info())
    metrics = {}
    for line in bytes(client.execute("METRICS")).decode().splitlines():
        if line and not line.startswith("#"):
            name, _, val = line.rpartition(" ")
            try:
                metrics[name] = float(val)
            except ValueError:
                pass
    devices = {k: dict(kv.split("=") for kv in v.split(","))
               for k, v in info.items() if k.startswith("device") and k[6:].isdigit()}
    return {"info": info, "metrics": metrics, "devices": devices}


def device_of(info: dict) -> dict:
    return {"platform": info["platform"], "kind": info["device_kind"],
            "count": int(info["local_device_count"])}


def memory_peak(snap: dict) -> int:
    return max([int(d.get("peak_bytes_in_use", 0)) for d in snap["devices"].values()] or [0])


# -- the traced slice ----------------------------------------------------------------


class Observations:
    """What the per-layer readers read."""

    frames = ()
    slice_latency_ms = ()
    slice_requests = 0
    slice_ops = 0
    device = None
    gen_late_ms = None


def trace_slice(server, client, trace_dir: str, t_start: float, seconds: float) -> dict:
    """Profile the device and collect the stage spans for a slice in the
    middle of the window (the parent has nothing else to do there)."""
    length = min(SLICE_MAX_S, SLICE_SHARE * seconds)
    begin = t_start + (seconds - length) / 2
    time.sleep(max(0.0, begin - time.monotonic()))
    started = server.control(f"trace-start {trace_dir}")
    client.execute("TRACE", "RESET")
    t0, wall0 = time.monotonic(), time.time()
    time.sleep(max(0.0, begin + length - time.monotonic()))
    t1, wall1 = time.monotonic(), time.time()
    reply = client.execute("TRACE", "GET", TRACE_RING)
    stopped = server.control("trace-stop", timeout=300.0)
    return {"t0": t0, "t1": t1, "wall0": wall0, "wall1": wall1, "reply": reply,
            "mark_wall_ns": started["mark_wall_ns"],
            "stop_wall_ns": stopped["stop_wall_ns"],
            "stop_took_s": stopped["stop_took_s"]}


def reduce_device_trace(trace_dir: str, platform: str, sl: dict) -> dict:
    """In a process of its own: reading the trace imports jax."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(HERE, "reduce_trace.py"), trace_dir, platform,
         "--mark-wall-ns", str(sl["mark_wall_ns"]), "--stop-wall-ns", str(sl["stop_wall_ns"])],
        capture_output=True, text=True, timeout=600, env=env, cwd=ROOT)
    if out.returncode != 0:
        raise BenchFailure("reduce_trace.py failed:\n" + out.stderr[-2000:])
    return json.loads(out.stdout.strip().splitlines()[-1])


def name_gaps(device: dict, frames: list) -> list:
    """The longest idle gaps, each named by the server stage that covered
    most of it (the spans' wall timestamps have 1 ms resolution)."""
    named = []
    spans = [(f["unix_ms"] * 1_000_000 + s["off_us"] * 1000,
              f["unix_ms"] * 1_000_000 + (s["off_us"] + s["dur_us"]) * 1000, s["name"])
             for f in frames for s in f["spans"] if not s["name"].endswith(".member")]
    for gap in device.get("gaps", []):
        label = f"device{gap['device']}"
        a = gap["start_wall_ns"]
        if a is not None and spans:
            b = a + int(gap["seconds"] * 1e9)
            cover = {}
            for s0, s1, name in spans:
                ov = min(b, s1) - max(a, s0)
                if ov > 0:
                    cover[name] = cover.get(name, 0) + ov
            label += ":" + (max(cover, key=cover.get) if cover else "no-frame-in-flight")
        named.append([label, gap["seconds"]])
    return named


# -- one run ----------------------------------------------------------------------------


def run(args) -> int:
    from benchmark import latency, loadgen, spans
    from benchmark.reduce_trace import find_xplane

    cell = load_cell(ROOT, args.workload, args.rehearse_cpu, args.set)
    sizes, params = cell["sizes"], cell["params"]
    try:
        import redisson_tpu  # noqa: F401 — jax-free; the program under test
    except ImportError as e:
        raise BenchFailure(f"the program is not here ({e}); nothing to measure") from None
    out_dir = os.path.join(ROOT, "chiprun_out", "benchmark", cell["name"])
    os.makedirs(out_dir, exist_ok=True)
    tag = f"seed{args.seed}-trace{args.trace}"
    gen = loadgen.load_generator(params["generator"])

    with tempfile.TemporaryDirectory(prefix="bench-") as tmp:
        server = Server(cell, args.rehearse_cpu, os.path.join(out_dir, tag + ".server.log"))
        workers = client = None
        try:
            spec = {"sizes": sizes, "params": params, "seed": args.seed, "ref_dir": tmp}
            workers = loadgen.Workers(spec)
            # the TPU runtime starts at the server's first device query, seconds
            # after READY: ask at once, and build the reference meanwhile
            first = {}

            def first_contact():
                try:
                    first["addr"] = server.wait_ready()
                    first["client"] = loadgen.connect(first["addr"])
                    first["device"] = device_of(parse_info(first["client"].info()))
                except BaseException as e:  # noqa: BLE001 — raised again below
                    first["error"] = e

            contact = threading.Thread(target=first_contact)
            contact.start()
            ref = gen.reference(sizes, params, args.seed)
            for name, arr in ref.items():
                np.save(os.path.join(tmp, name + ".npy"), arr)
            ref_s = time.monotonic() - _T0
            contact.join()
            client = first.get("client")
            if "error" in first:
                raise first["error"]
            addr, device = first["addr"], first["device"]
            timeline = {"reference": ref_s, "ready": server.boot_s,
                        "device_known": time.monotonic() - _T0}
            log(f"server READY after {server.boot_s:.1f}s, reference after {ref_s:.1f}s, "
                f"device known after {timeline['device_known']:.1f}s")
            want = "cpu" if args.rehearse_cpu else "tpu"
            if device["platform"] != want or device["count"] != cell["chips"]:
                raise BenchFailure(
                    f"the server runs on {device['count']} x {device['platform']!r}; "
                    f"the cell needs {cell['chips']} x {want!r} (no accelerator, no "
                    "result; --rehearse-cpu rehearses the script on the CPU)")
            if not args.rehearse_cpu and device["kind"] not in _json(
                    os.path.join(HERE, "peaks.json")):
                raise BenchFailure(f"no peaks for device kind {device['kind']!r} in "
                                   "benchmark/peaks.json: add them with their source")
            populated = gen.populate(client, sizes, params, args.seed)
            timeline["populated"] = time.monotonic() - _T0
            workers.connect(addr)
            workers.warm()
            timeline["warmed"] = time.monotonic() - _T0
            populate_s = timeline["populated"] - timeline["device_known"]
            warm_s = timeline["warmed"] - timeline["populated"]
            if args.trace:
                client.execute("CONFIG", "SET", "trace-ring-capacity", TRACE_RING)
                client.execute("CONFIG", "SET", "trace-enabled", "yes")
            before = snapshot(client)
            t_start = time.monotonic() + 0.25
            setup_s = t_start - _T0
            log(f"set-up {setup_s:.1f}s (populate {populate_s:.1f}s, warm-up {warm_s:.1f}s, "
                f"compile {before['info']['compile_seconds']}s, "
                f"{before['info']['compiled_programs']} programs); measuring {args.seconds}s")
            workers.go(t_start, t_start + args.seconds)
            sl = None
            if args.trace:
                sl = trace_slice(server, client, os.path.join(tmp, "trace"),
                                 t_start, args.seconds)
            writes = workers.ran(args.seconds + loadgen.DRAIN_LIMIT_S + 300.0)
            after = snapshot(client)
            failures, extra = gen.after_window(client, sizes, params, args.seed, ref, writes)
            for name, arr in extra.items():
                np.save(os.path.join(tmp, name + ".npy"), arr)
            reports = workers.verify()
            exit_s, rc = server.stop()  # SIGTERM with the client connection open
            dev_trace = None
            if sl is not None:
                dev_trace = reduce_device_trace(os.path.join(tmp, "trace"),
                                                device["platform"], sl)
                if args.keep_trace:
                    xplane = find_xplane(os.path.join(tmp, "trace"))
                    log(f"trace file: {os.path.getsize(xplane)} bytes")
                    if os.path.getsize(xplane) < (24 << 20):
                        shutil.copy(xplane, os.path.join(out_dir, tag + ".xplane.pb"))
        finally:
            if client is not None:
                client.shutdown()
            if workers is not None:
                workers.stop()
            server.close()

    # -- reduce ----------------------------------------------------------------------------
    if "jax" in sys.modules:  # one process per chip: the parent stays off jax
        raise BenchFailure("the parent imported jax")
    rows = np.concatenate([r["rows"] for r in reports]).reshape(-1, 7)
    if args.keep_rows:  # (idx, t_ref, t_send, t_done, free_at, ops, ok) a request
        np.save(os.path.join(out_dir, tag + ".rows.npy"),
                rows - np.array([0, t_start, t_start, t_start, t_start, 0, 0]))
    ok = rows[:, 6] == 1
    unsent = sum(r["unsent"] for r in reports)
    attempted = len(rows) + unsent
    failed = int((~ok).sum()) + unsent
    for r in reports:
        failures += r["failures"] + r["errors"]
    latency_ms = latency.request_ms(rows)
    judged_ms = latency.judged_ms(reports, params, gen)  # the requests', unless the mix says
    elapsed = max(float(args.seconds), float(rows[:, 3].max() - t_start)) if len(rows) else 0.0
    ops_per_s = float(rows[ok, 5].sum()) / elapsed if elapsed else 0.0
    i0, i1, m0, m1 = before["info"], after["info"], before["metrics"], after["metrics"]
    new_programs = int(i1["compiled_programs"]) - int(i0["compiled_programs"])
    if new_programs:
        failures.append(f"{new_programs} programs compiled inside the window")
    for key in MUST_BE_ZERO:
        if int(i1[key]):
            failures.append(f"{key}={i1[key]}")
    errs = (int(i1["errors"]) - int(i0["errors"]),
            m1.get("rtpu_commands_errors", 0) - m0.get("rtpu_commands_errors", 0),
            m1.get("rtpu_qos_shed_ops", 0) - m0.get("rtpu_qos_shed_ops", 0))
    if any(errs):
        failures.append(f"error replies / command errors / shed ops in the window: {errs}")
    if i1["replica_occupancy"] != "none":
        failures.append(f"the sleep model is armed ({i1['replica_occupancy']} ns/item)")
    if i1["native_build"] in ("build_failed", "load_failed"):
        failures.append(f"native wire build {i1['native_build']}")
    if rc != 0:
        failures.append(f"server exit code {rc} after SIGTERM ({exit_s:.1f}s)")
    if not len(latency_ms):
        failures.append("no request was answered")
    elif not len(judged_ms):
        failures.append(f"no complete {params['latency_over']} in the window")

    e2e = {
        "ops_per_s": ops_per_s,
        "req_p50_ms": float(np.median(judged_ms)) if len(judged_ms) else 0.0,
        "req_p95_ms": float(np.percentile(judged_ms, 95)) if len(judged_ms) else 0.0,
        "req_p99_ms": float(np.percentile(judged_ms, 99)) if len(judged_ms) else 0.0,
        "req_max_ms": float(judged_ms.max()) if len(judged_ms) else 0.0,
        "setup_s": setup_s,
    }
    obs = Observations()
    obs.latency_ms, obs.judged_ms, obs.ops_per_s = latency_ms, judged_ms, ops_per_s
    obs.request_ops, obs.params = rows[ok, 5], params
    obs.metrics_before, obs.metrics_after = m0, m1
    obs.memory_peak_bytes = memory_peak(after)
    if params["loop"] == "open":
        obs.gen_late_ms = (rows[ok, 2] - rows[ok, 4]) * 1e3
    out_device = {**device, "memory_peak_bytes": obs.memory_peak_bytes}
    breakdown = None
    if sl is not None:
        obs.frames = [f for f in spans.parse_frames(sl["reply"])
                      if sl["wall0"] * 1e3 <= f["unix_ms"] <= sl["wall1"] * 1e3]
        inside = ok & (rows[:, 3] >= sl["t0"]) & (rows[:, 3] <= sl["t1"])
        obs.slice_latency_ms = (rows[inside, 3] - rows[inside, 1]) * 1e3
        obs.slice_requests = int(inside.sum())
        obs.slice_ops = float(rows[inside, 5].sum()) * (
            dev_trace["window_s"] / (sl["t1"] - sl["t0"]) if dev_trace["window_s"] else 1.0)
        obs.device = dev_trace
        if not dev_trace["devices"] or not sum(dev_trace["busy_s"]):
            raise BenchFailure("the trace shows no operation on the device")
        out_device["busy_s"] = sum(dev_trace["busy_s"]) / len(dev_trace["busy_s"])
        out_device["window_s"] = dev_trace["window_s"]
        breakdown = {"device_ops": [[n, s] for n, s in
                                    (dev_trace["programs"] or dev_trace["ops"])],
                     "idle_gaps": name_gaps(dev_trace, obs.frames)}
        metrics = {}
        for m in cell["per_layer"]:
            value = load_reader(m["name"])(obs)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell["end_to_end"]}

    detail = {
        "cell": cell["name"], "seed": args.seed, "seconds": args.seconds,
        "trace": args.trace, "rehearsal": args.rehearse_cpu,
        "setup": {"setup_s": setup_s, "boot_s": server.boot_s, "reference_s": ref_s,
                  "populate_s": populate_s, "warm_s": warm_s,
                  "compile_seconds": float(i0["compile_seconds"]),
                  "compiled_programs": int(i0["compiled_programs"]),
                  "cache_hits": int(i0["compile_cache_hits"]),
                  "cache_writes": int(i0["compile_cache_writes"]),
                  "server_exit_s": exit_s, "timeline": timeline, **populated},
        "client": {**e2e, "samples": int(len(judged_ms)), "requests": int(len(latency_ms)),
                   "elapsed_s": elapsed,
                   "gen_late_p99_ms": (float(np.percentile(obs.gen_late_ms, 99))
                                       if obs.gen_late_ms is not None and len(obs.gen_late_ms)
                                       else None),
                   "checked": sum(r["checked"] for r in reports),
                   "checked_in_full": sum(r["checked_full"] for r in reports),
                   **{k: v.tolist() for k, v in extra.items() if v.size == 1}},
        "server": {"wire_plane": i1["wire_plane"], "native_build": i1["native_build"],
                   "staging_reuses": int(i1["staging_reuses"]),
                   "d2d_colocations": int(i1["d2d_colocations"]),
                   "devices": after["devices"]},
        "failures": failures[:20],
    }
    if sl is not None:
        detail["slice"] = {"seconds": sl["t1"] - sl["t0"], "frames": len(obs.frames),
                           "requests": obs.slice_requests, "device": dev_trace,
                           "stop_trace_took_s": sl["stop_took_s"]}
    with open(os.path.join(out_dir, tag + ".json"), "w") as fh:
        json.dump(detail, fh, indent=1)
    print("DETAIL " + json.dumps(detail))
    last = {"correct": bool(not failures and not failed and not args.rehearse_cpu),
            "attempted": int(attempted), "failed": int(failed),
            "metrics": metrics, "device": out_device}
    if breakdown is not None:
        last["breakdown"] = breakdown
    if args.rehearse_cpu:
        last["rehearsal"] = "cpu: a rehearsal of the script at tiny size, not a measurement"
    print(json.dumps(last), flush=True)
    return 0


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="rehearse the script on the CPU at tiny size; prints "
                         "platform cpu and correct:false — never a measurement")
    ap.add_argument("--keep-trace", action="store_true",
                    help="copy a traced run's .xplane.pb beside its other outputs")
    ap.add_argument("--keep-rows", action="store_true",
                    help="save every request's clock readings beside the other outputs")
    ap.add_argument("--set", action="append", default=[], metavar="KEY=JSON",
                    help="override one traffic parameter (the knee sweep's rate)")
    args = ap.parse_args()
    try:
        return run(args)
    except BenchFailure as e:
        print(f"benchmark: FAILED — {e}", file=sys.stderr)
        return 3


if __name__ == "__main__":
    sys.exit(main())
