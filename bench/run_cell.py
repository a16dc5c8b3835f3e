#!/usr/bin/env python3
"""Run one benchmark cell and print its result as the last stdout line.

    python3 bench/run_cell.py --workload <cell> --seed <n> --seconds <s> \\
        --trace <0|1>

Everything the cell needs is found by name from ``BENCHMARK.json`` (see
``bench/lib/spec.py``). This process never imports JAX: it generates the
traffic from ``--seed``, starts ``bench/serve_cell.py`` (the one process
that holds the chip), drives the traffic over TCP as a remote client
would, and takes every end-to-end number at the client:

    tokens_per_s   output tokens that reached the client in the window,
                   over the window's length
    ttft_p95_ms    95th percentile, over the requests whose send was due in
                   the window, of due time to first token at the client
    itl_p95_ms     95th percentile of every gap between consecutive tokens
                   of a request that ended in the window
    setup_s        process start to window start: build, compile, warm-up
                   and the traffic's ramp

The window opens ``ramp_s`` after the first request and lasts
``--seconds``. Load goes on after it until enough requests have received
every token they asked for and, in an open loop, every request due in the
window has its first token (60 s at most). Then a seeded sample of those
requests goes to the plain reference (``bench/lib/check.py``); ``correct`` is the
comparison against the cell's limits, printed as the last stderr lines and
as the result's last key. With ``--trace 1`` the profiler records a
sub-window of ``trace_s`` seconds in the middle of the window, and the
result carries the per-layer metrics instead of the end-to-end ones.

``--control 1`` (for setting limits, never in a benchmark run) judges
the control in the program's place: the plain reference computed one
precision step below the served dtype, read at the same served
positions. A sound limit makes that run come out not correct. The
program's own widest gap is then logged to stderr beside it.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from bench.lib import check, stats  # noqa: E402
from bench.lib import traffic as tgen  # noqa: E402
from bench.lib import trace as tred  # noqa: E402
from bench.lib.client import Connection  # noqa: E402
from bench.lib.spec import Bench, SpecError, read_metric  # noqa: E402

GRACE_S = 60.0          # after the window: wait for its first tokens
READY_TIMEOUT_S = 1100  # a cold first run compiles every program
RUNS_DIR = ".bench_runs"   # under the checkout; git-ignored


def log(msg: str) -> None:
    print(f"run_cell: {msg}", file=sys.stderr, flush=True)


class Child:
    """The chip-holding process, driven one JSON line at a time."""

    def __init__(self, cmd: list, spec: dict, cwd: str):
        self.proc = subprocess.Popen(cmd, stdin=subprocess.PIPE,
                                     stdout=subprocess.PIPE, text=True,
                                     cwd=cwd)
        self.send(spec)

    def send(self, obj: dict) -> None:
        self.proc.stdin.write(json.dumps(obj) + "\n")
        self.proc.stdin.flush()

    def recv(self, timeout: float) -> dict:
        box: list = []
        th = threading.Thread(target=lambda: box.append(
            self.proc.stdout.readline()), daemon=True)
        th.start()
        th.join(timeout)
        if not box or not box[0]:
            raise RuntimeError("serve_cell ended or timed out without "
                               f"replying (exit {self.proc.poll()})")
        return json.loads(box[0])

    def call(self, obj: dict, timeout: float = 120.0) -> dict:
        self.send(obj)
        return self.recv(timeout)

    def stop(self) -> int:
        if self.proc.poll() is None:
            try:
                self.proc.stdin.close()
            except OSError:
                pass
            try:
                self.proc.wait(30)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        return self.proc.returncode


class Load:
    """Sends the plan's requests and records, for each, what the client
    saw: due time, send time, every token's arrival, the final status."""

    def __init__(self, conn: Connection, reqs: list, loop: str,
                 in_flight: int):
        self.conn, self.reqs = conn, reqs
        self.loop, self.in_flight = loop, in_flight
        self.rid2i: dict = {}
        self.lock = threading.Lock()
        self.freed = threading.Semaphore(0)
        self.next = 0
        for r in reqs:
            r.update(due=None, sent=None, times=[], tokens=[], status=None)
        self.reader = threading.Thread(target=self._read, daemon=True)
        self.reader.start()

    def _read(self) -> None:
        try:
            for ev in self.conn.events():
                now = time.monotonic()
                kind = ev.get("event")
                with self.lock:
                    if kind == "submitted":
                        self.rid2i[ev["rid"]] = ev["tag"]
                        continue
                    r = self.reqs[self.rid2i[ev["rid"]]] \
                        if ev.get("rid") in self.rid2i else None
                    if r is None:
                        continue
                    if kind == "token":
                        r["times"].append(now)
                        r["tokens"].append(ev["token"])
                    elif kind == "done":
                        r["status"], r["done_t"] = ev["status"], now
                        r["error"] = ev.get("error")
                if kind == "done":
                    self.freed.release()
        except (OSError, ValueError):
            pass

    def send_next(self, due: float) -> None:
        r = self.reqs[self.next]
        self.next += 1
        with self.lock:
            r["due"], r["sent"] = due, time.monotonic()
        self.conn.submit(r["prompt"], r["max_new"], tag=r["i"])

    def pump(self, t0: float, until: float) -> None:
        """Send what is due before ``until`` (monotonic seconds)."""
        while self.next < len(self.reqs):
            if self.loop == "open":
                due = t0 + self.reqs[self.next]["t"]
                if due >= until:
                    break
                time.sleep(max(0.0, due - time.monotonic()))
                self.send_next(due)
            else:
                if self.next < self.in_flight:
                    self.send_next(time.monotonic())
                    continue
                wait = until - time.monotonic()
                if wait <= 0 or not self.freed.acquire(timeout=wait):
                    return
                self.send_next(time.monotonic())
        time.sleep(max(0.0, until - time.monotonic()))


class Control(threading.Thread):
    """Opens and closes the window in the child (compiles are counted in
    it) and, for a traced run, starts and stops the profiler around a
    sub-window in its middle, off the load generator's thread."""

    def __init__(self, child: Child, w0: float, w1: float, trace_s: float):
        super().__init__(daemon=True)
        self.child, self.error = child, None
        ts = w0 + (w1 - w0 - trace_s) / 2
        self.plan = [(w0, {"cmd": "window", "open": True})]
        if trace_s:
            self.plan += [(ts, {"cmd": "trace_start"}),
                          (ts + trace_s, {"cmd": "trace_stop"})]
        self.plan.append((w1, {"cmd": "window", "open": False}))
        self.start()

    def run(self) -> None:
        try:
            for at, cmd in self.plan:
                time.sleep(max(0.0, at - time.monotonic()))
                self.child.call(cmd)
        except (RuntimeError, OSError, ValueError) as e:
            self.error = e

    def join(self, timeout=None) -> None:
        super().join(timeout)
        if self.error is not None:
            raise RuntimeError(f"child command failed: {self.error!r}")


def complete(r: dict) -> bool:
    """Finished, with every token it asked for streamed to the client."""
    return r["status"] == "finished" and len(r["tokens"]) == r["max_new"]


def end_to_end(reqs: list, w0: float, w1: float) -> tuple:
    """(metrics, attempted, failed, lateness) over the window [w0, w1).

    Open loop: the requests due in the window are attempted; one fails if
    it was refused or aborted, or never had its first token. Closed loop:
    the requests live in the window are attempted, and one fails only if
    refused or aborted, since a request the loop sends waits in the
    engine's queue by design."""
    sent = [r for r in reqs if r["sent"] is not None]
    due_in = [r for r in sent if w0 <= r["due"] < w1]
    if reqs and reqs[0]["t"] is not None:
        pool = due_in
        failed = sum(1 for r in pool if r["status"] in ("rejected",
                                                        "aborted")
                     or not r["times"])
    else:
        pool = [r for r in sent if r["sent"] < w1
                and not (r["status"] and r["done_t"] < w0)]
        failed = sum(1 for r in pool if r["status"] in ("rejected",
                                                        "aborted"))
    attempted = len(pool)
    ttft = [(r["times"][0] - r["due"]) * 1e3 for r in due_in if r["times"]]
    gaps = [(b - a) * 1e3 for r in sent
            for a, b in zip(r["times"], r["times"][1:]) if w0 <= b < w1]
    arrivals = [t for r in sent for t in r["times"]]
    metrics = {
        "tokens_per_s": stats.rate(arrivals, w0, w1),
        "ttft_p95_ms": stats.percentile(ttft, 95),
        "itl_p95_ms": stats.percentile(gaps, 95),
    }
    late = max((r["sent"] - r["due"] for r in due_in), default=0.0)
    return metrics, attempted, failed, late


def per_layer(bench: Bench, cell: dict, cfg: dict, trace_file: str,
              peak: dict) -> tuple:
    with open(trace_file) as f:
        rec = json.load(f)
    ctx = {"trace": rec["trace"], "obs": rec["obs"],
           "window_s": rec["window_s"], "config": cfg, "peak": peak,
           "chips": cell["chips"]}
    metrics = {}
    for m in bench.per_layer(cell["name"]):
        v = read_metric(bench, m["name"], ctx)
        if v is not None:
            metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    red = tred.reduce(rec["trace"], kernel="$^", step_module="$^") \
        if rec["trace"] else None
    return metrics, red


def run(argv=None, child_cmd=None, root: str = ROOT) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="also report the control's gaps (limit setting)")
    args = ap.parse_args(argv)

    bench = Bench(root)
    cell = bench.cell(args.workload)
    cfg = bench.config(cell["config"])
    traffic = bench.traffic(cell["traffic"])
    limits = bench.limits(cell["name"])
    peaks = bench.peaks()
    eng = cfg["engine"]
    reqs = tgen.plan(traffic, vocab=cfg["vocab_size"], seed=args.seed,
                     seconds=args.seconds, n_slots=eng["n_slots"],
                     grace_s=GRACE_S)
    run_dir = os.path.join(root, RUNS_DIR, cell["name"])
    shutil.rmtree(run_dir, ignore_errors=True)
    os.makedirs(run_dir)
    spec = {"config": cfg, "seed": args.seed, "trace": bool(args.trace),
            "chips": cell["chips"], "peak_kinds": sorted(peaks),
            "prompt_lens": sorted({len(r["prompt"]) for r in reqs}),
            "ref_pad": traffic["prompt_len"]["max"]
            + traffic["output_len"]["max"],
            "run_dir": run_dir}
    cmd = child_cmd or [sys.executable, os.path.join(HERE, "serve_cell.py")]
    child = Child(cmd, spec, root)
    conn = None
    try:
        ready = child.recv(READY_TIMEOUT_S)
        dev = ready["device"]
        conn = Connection("127.0.0.1", ready["port"])
        load = Load(conn, reqs, traffic["loop"],
                    tgen.in_flight(traffic, eng["n_slots"])
                    if traffic["loop"] == "closed" else 0)
        t0 = time.monotonic()
        w0 = t0 + traffic["ramp_s"]
        w1 = w0 + args.seconds
        ctl = Control(child, w0, w1, traffic["trace_s"] if args.trace
                      else 0.0)
        load.pump(t0, w1)
        ctl.join()
        setup_s = w0 - T_PROCESS
        # keep the load on until what was due in the window has started
        # and enough requests have finished for the output check
        deadline = w1 + GRACE_S
        open_loop = traffic["loop"] == "open"
        while time.monotonic() < deadline:
            with load.lock:
                waiting = open_loop and any(
                    r["due"] is not None and w0 <= r["due"] < w1
                    and not r["times"] and r["status"] is None
                    for r in reqs)
                n_done = sum(complete(r) for r in reqs)
            if not waiting and n_done >= traffic["check_requests"]:
                break
            load.pump(t0, min(deadline, time.monotonic() + 0.5))
        with load.lock:
            metrics, attempted, failed, late = end_to_end(reqs, w0, w1)
            done = [dict(r) for r in reqs if complete(r)]
            short = sum(r["status"] == "finished" and not complete(r)
                        for r in reqs)
            ends: dict = {}
            for r in reqs:
                if r["sent"] is not None:
                    ends[str(r["status"])] = ends.get(str(r["status"]), 0) + 1
        conn.close()
        conn = None
        sample = check.choose_sample(done, traffic["check_requests"],
                                     args.seed)
        res = child.call({"cmd": "finish", "control": bool(args.control),
                          "sample": [[r["prompt"].tolist(), r["tokens"]]
                                     for r in sample]}, timeout=600)
    except (RuntimeError, OSError, ValueError, KeyError) as e:
        log(f"run failed: {e!r}")
        if conn is not None:
            conn.close()
        child.stop()
        return 1
    rc = child.stop()
    if rc != 0:
        log(f"serve_cell exited {rc}")
        return 1

    # the control is judged in the program's place: it has to come out
    # as not correct
    gaps = res.get("control_gaps" if args.control else "gaps", [])
    ok, checks = check.compare(gaps, limits)
    ok = ok and attempted > 0
    device = {"platform": dev["platform"], "kind": dev["kind"],
              "count": dev["count"],
              "memory_peak_bytes": res["memory_peak_bytes"]}
    out = {"correct": ok, "attempted": attempted, "failed": failed}
    if args.trace:
        layer, red = per_layer(bench, cell, cfg, res["trace_file"],
                               peaks[dev["kind"]])
        out["metrics"] = layer
        if red is not None:
            device.update(busy_s=red["busy_s"], window_s=red["span_s"])
            out["breakdown"] = {"device_ops": red["device_ops"],
                                "idle_gaps": red["idle_gaps"]}
    else:
        metrics["setup_s"] = setup_s
        units = {m["name"]: m["unit"] for m in bench.end_to_end(cell["name"])}
        out["metrics"] = {k: {"value": v, "unit": units[k]}
                          for k, v in metrics.items()
                          if k in units and v is not None}
    out["device"] = device
    log(f"window {args.seconds:g} s: attempted {attempted}, failed {failed},"
        f" generator late by up to {late * 1e3:.1f} ms, compiles in window "
        f"{res['compiles_in_window']}, reference {res['reference_s']:.1f} s,"
        f" sample {len(sample)} of {len(done)} complete requests"
        f" ({short} finished short of their max_new); sent requests by "
        f"status {ends}")
    if args.control:
        log("program widest gap (the control is judged below) "
            f"{check.compare(res.get('gaps', []), limits)[1]}")
    out["checks"] = checks
    for name, c in checks.items():
        print(f"check {name}: {c['value']} (limit {c['limit']}, "
              f"{check.RULES[name]})", file=sys.stderr, flush=True)
    print(json.dumps(out), flush=True)
    return 0


def main() -> int:
    try:
        return run()
    except SpecError as e:
        log(str(e))
        return 2


if __name__ == "__main__":
    sys.exit(main())
