"""Newline-JSON TCP serving frontend over the AsyncEngine host loop.

Run (port 0 picks a free port; the bound port is printed)::

    PYTHONPATH=src python -m repro.launch.server --port 0 \\
        --config tiny-dense --paged --page-size 4 --max-len 48 --n-slots 2

The server prints exactly one ``LISTENING <port>`` line to stdout once it
is accepting connections, then serves until SIGINT/SIGTERM or a client
``shutdown`` op. Params are ``init_params(PRNGKey(--seed), cfg)``, so a
client holding the same (config, seed) pair can recompute ``generate()``
references for token-exact parity checks (the CI smoke does).

PROTOCOL — one UTF-8 JSON object per ``\\n``-terminated line, both ways.
Multiple requests may be in flight per connection; every server event
carries the ``rid`` it belongs to, so streams interleave safely.

client -> server ops::

    {"op": "submit", "prompt": [int, ...], "max_new": int,
     "stream": bool (default true), "tag": any (echoed back),
     "spec_gamma": int (default 0), "draft_m": int | null}
        spec_gamma > 0 opts the request into speculative decoding —
        served only when the server registered a drafter (--draft-m);
        draft_m picks the registered linearization depth. An unservable
        spec submission (no drafter, temperature > 0, span past max_len)
        is rejected-with-error like any other bad submit.
    {"op": "cancel", "rid": int}     cancel in ANY lifecycle state; scoped
                                     to rids submitted on THIS connection
    {"op": "stats"}                  engine stats() + allocator occupancy
    {"op": "metrics"}                live observability scrape (see below)
    {"op": "ping"}
    {"op": "shutdown"}               drain the engine and stop the server

server -> client events::

    {"event": "submitted", "rid": int, "tag": ...}
    {"event": "token", "rid": int, "index": int, "token": int}
        (only when "stream" was true; index is the position in the
         generated sequence — contiguous from 0, preemption-safe)
    {"event": "done", "rid": int, "status": "finished" | "cancelled" |
     "rejected" | "aborted", "tokens": [int, ...], "error": str | null}
        ("tokens" is the full generation — partial if cancelled; a
         rejected submission goes straight to "done" with "error" set:
         rejection is an event, never a dropped connection)
    {"event": "cancelling", "rid": int}     cancel op acknowledged
    {"event": "stats", "stats": {...}}
    {"event": "metrics", "enabled": bool, "metrics": {...},
     "prometheus": str}
        Consistent point-in-time scrape of the engine's observability
        registry (repro.obs): "metrics" is the JSON snapshot — {"labels"
        (engine_mode / nbl_m), "counters", "gauges", "histograms"
        (cumulative [upper_bound, count] pairs), "last_step" (the newest
        step-timeline record)} — and "prometheus" is the SAME scrape in
        Prometheus text exposition format (# HELP / # TYPE / series
        lines), ready to proxy to any Prometheus scraper. Observability
        is ON by default (--no-obs disables it; the scrape then returns
        {"enabled": false} only). --trace-out FILE additionally exports
        the per-request Chrome-trace/Perfetto timeline at shutdown.
    {"event": "pong"} / {"event": "bye"}
    {"event": "error", "error": str}        malformed line; connection
                                            stays up

Disconnect semantics: when a connection drops, every request it submitted
that is not yet terminal is CANCELLED — its pages and shared-prefix pins
are unref'd by the engine's cancel path, so a vanishing client can never
leak pool pages (the lifecycle bug this frontend exists to force out).
"""
from __future__ import annotations

import argparse
import json
import signal
import socket
import sys
import threading
from typing import Optional

import numpy as np

from repro.launch.engine import AsyncEngine, Engine, Stream


def _jsonable(d: dict) -> dict:
    return {k: (v.item() if hasattr(v, "item") else v) for k, v in d.items()}


class NBLServer:
    """Threaded newline-JSON TCP frontend: one handler thread per
    connection, one pump thread per submitted stream (writes are serialized
    per connection). All engine interaction goes through the AsyncEngine's
    thread-safe surface."""

    def __init__(self, aeng: AsyncEngine, host: str = "127.0.0.1",
                 port: int = 0, backlog: int = 16):
        self.aeng = aeng
        self._sock = socket.socket(socket.AF_INET, socket.SOCK_STREAM)
        self._sock.setsockopt(socket.SOL_SOCKET, socket.SO_REUSEADDR, 1)
        self._sock.bind((host, port))
        self._sock.listen(backlog)
        self.host, self.port = self._sock.getsockname()[:2]
        self._stop = threading.Event()
        self._down = False                   # guarded-by: _down_lock
        self._down_lock = threading.Lock()

    def request_stop(self) -> None:
        """Signal-safe stop request: flips the stop flag and closes the
        listening socket WITHOUT taking the shutdown lock — a signal
        handler runs re-entrantly on the main thread's stack, where
        acquiring the non-reentrant lock the interrupted frame may already
        hold would self-deadlock. serve_forever() notices within its
        accept timeout and the caller's normal shutdown() path finishes
        the job."""
        self._stop.set()
        try:
            self._sock.close()
        except OSError:
            pass

    def serve_forever(self) -> None:
        """Accept loop; returns after ``shutdown()`` (any thread). The
        accept blocks with a timeout: closing a listening socket from
        another thread does not wake a blocked accept() on Linux, so the
        loop polls the stop flag instead."""
        self._sock.settimeout(0.2)
        while not self._stop.is_set():
            try:
                conn, _ = self._sock.accept()
            except TimeoutError:             # poll the stop flag
                continue
            except OSError:
                break                        # listening socket closed
            conn.settimeout(None)            # accept() timeout not inherited
            threading.Thread(target=self._serve_conn, args=(conn,),
                             daemon=True).start()

    def shutdown(self, *, drain: bool = True) -> None:
        """Stop accepting, then stop the engine host loop (``drain`` as in
        ``AsyncEngine.shutdown``). SERIALIZED: a concurrent caller blocks
        until the first shutdown completes instead of re-entering with its
        own drain flag — otherwise main()'s abort-on-exit would downgrade
        a client-requested drain mid-flight, cancelling work the protocol
        promised to finish. Idempotent ONLY once the engine stopped
        cleanly: if its step loop died, every call re-raises — so a
        client-triggered shutdown raising in a handler thread does not
        eat the failure; ``main()``'s own shutdown call sees it again and
        exits nonzero."""
        with self._down_lock:
            if self._down:
                return
            self._stop.set()
            try:
                self._sock.close()
            except OSError:
                pass
            self.aeng.shutdown(drain=drain)  # may raise: _down stays False
            self._down = True

    # ------------------------------------------------------ per-connection

    def _serve_conn(self, conn: socket.socket) -> None:
        wlock = threading.Lock()

        def send(obj: dict) -> None:
            data = (json.dumps(obj) + "\n").encode()
            with wlock:
                try:
                    conn.sendall(data)
                except OSError:
                    pass                     # client gone; cleanup below

        owned: list[Stream] = []             # this connection's submissions
        try:
            reader = conn.makefile("r", encoding="utf-8")
            for line in reader:
                line = line.strip()
                if not line:
                    continue
                try:
                    msg = json.loads(line)
                    op = msg["op"]
                except Exception as e:       # malformed line, not fatal
                    send({"event": "error", "error": f"bad request: {e}"})
                    continue
                if op == "submit":
                    self._op_submit(msg, send, owned)
                elif op == "cancel":
                    try:
                        rid = int(msg["rid"])
                    except Exception as e:
                        send({"event": "error",
                              "error": f"bad cancel: {e}"})
                        continue
                    if rid not in {s.rid for s in owned}:
                        # scoped to the submitting connection: rids are
                        # guessable sequential ints, and nothing should
                        # let one client cancel another's request
                        send({"event": "error",
                              "error": f"unknown rid {rid} (cancel is "
                                       f"per-connection)"})
                        continue
                    self.aeng.cancel(rid)
                    send({"event": "cancelling", "rid": rid})
                elif op == "stats":
                    send({"event": "stats",
                          "stats": _jsonable(self.aeng.stats())})
                elif op == "metrics":
                    obs = self.aeng.engine.obs
                    if obs is None:
                        send({"event": "metrics", "enabled": False})
                    else:
                        send({"event": "metrics", "enabled": True,
                              "metrics": obs.snapshot(),
                              "prometheus": obs.render_prometheus()})
                elif op == "ping":
                    send({"event": "pong"})
                elif op == "shutdown":
                    send({"event": "bye"})
                    self.shutdown(drain=True)
                    break
                else:
                    send({"event": "error", "error": f"unknown op {op!r}"})
        finally:
            # disconnect cancels everything this connection still has in
            # flight — pages/pins unref'd, nothing leaks
            for s in owned:
                if not s.done:
                    self.aeng.cancel(s.rid)
            try:
                conn.close()
            except OSError:
                pass

    def _op_submit(self, msg: dict, send, owned: list) -> None:
        try:
            prompt = np.asarray(msg["prompt"], np.int32).reshape(-1)
            max_new = int(msg["max_new"])
            spec_gamma = int(msg.get("spec_gamma", 0))
            draft_m = msg.get("draft_m")
            draft_m = int(draft_m) if draft_m is not None else None
        except Exception as e:
            send({"event": "error", "error": f"bad submit: {e}"})
            return
        want_stream = bool(msg.get("stream", True))
        # prune terminal streams first: a long-lived connection otherwise
        # grows `owned` (each entry holding its full token list) without
        # bound — the disconnect-cancel and cancel-scoping scans only need
        # the live ones, plus whatever finished since the last submit
        owned[:] = [t for t in owned if not t.done]
        try:
            s = self.aeng.submit_stream(prompt, max_new,
                                        spec_gamma=spec_gamma,
                                        draft_m=draft_m)
        except RuntimeError as e:
            # engine shut down / step loop died: still an EVENT (the
            # docstring's promise), never a dropped connection
            send({"event": "error", "error": f"submit failed: {e}"})
            return
        owned.append(s)
        send({"event": "submitted", "rid": s.rid, "tag": msg.get("tag")})

        def pump() -> None:
            for i, tok in enumerate(s):
                if want_stream:
                    send({"event": "token", "rid": s.rid, "index": i,
                          "token": tok})
            send({"event": "done", "rid": s.rid, "status": s.status,
                  "tokens": [int(t) for t in s.tokens], "error": s.error})

        threading.Thread(target=pump, daemon=True).start()


def _build_engine(args) -> Engine:
    import jax

    from repro.configs import get_config
    from repro.models import init_params

    cfg = get_config(args.config)
    params = init_params(jax.random.PRNGKey(args.seed), cfg)
    kw: dict = {}
    if args.paged or args.prefix_sharing or args.chunked_prefill:
        kw.update(paged=True, page_size=args.page_size)
    if args.prefix_sharing:
        kw.update(prefix_sharing=True,
                  shared_prefix_len=args.shared_prefix_len)
    if args.chunked_prefill:
        kw.update(chunked_prefill=True)
        if args.prefill_chunk_tokens is not None:
            kw.update(prefill_chunk_tokens=args.prefill_chunk_tokens)
    if args.draft_m is not None:
        # zero-map NBL drafter: structurally complete (and exactness holds
        # regardless of draft quality), so the server needs no calibration
        # pass — a calibrated registry is a deployment concern
        from repro.launch.speculative import make_nbl_draft
        kw.update(paged=True, page_size=args.page_size,
                  drafts={args.draft_m:
                          make_nbl_draft(cfg, params, args.draft_m)})
    if args.expected_len is not None:
        kw.update(expected_len=args.expected_len)
    if not args.no_obs:
        from repro.obs import Observability
        kw.update(obs=Observability(
            trace_annotations=args.trace_annotations))
    n_slots = args.n_slots
    budget = (int(args.cache_budget_mb * 2**20)
              if args.cache_budget_mb is not None else None)
    if n_slots is None and budget is None:
        n_slots = 4
    return Engine(cfg, params, max_len=args.max_len, n_slots=n_slots,
                  cache_budget_bytes=budget, eos_id=args.eos_id,
                  temperature=args.temperature, seed=args.seed, **kw)


def main(argv: Optional[list] = None) -> int:
    ap = argparse.ArgumentParser(
        description="newline-JSON TCP serving frontend (see module "
                    "docstring for the protocol)")
    ap.add_argument("--host", default="127.0.0.1")
    ap.add_argument("--port", type=int, default=0,
                    help="0 = pick a free port (printed as LISTENING <p>)")
    ap.add_argument("--config", default="tiny-dense")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--max-len", type=int, default=64)
    ap.add_argument("--n-slots", type=int, default=None)
    ap.add_argument("--cache-budget-mb", type=float, default=None,
                    help="NBL-aware HBM budget instead of --n-slots")
    ap.add_argument("--expected-len", type=int, default=None)
    ap.add_argument("--eos-id", type=int, default=None)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--paged", action="store_true")
    ap.add_argument("--page-size", type=int, default=8)
    ap.add_argument("--prefix-sharing", action="store_true")
    ap.add_argument("--shared-prefix-len", type=int, default=0)
    ap.add_argument("--chunked-prefill", action="store_true")
    ap.add_argument("--prefill-chunk-tokens", type=int, default=None)
    ap.add_argument("--draft-m", type=int, default=None,
                    help="register an m-deepest-layers NBL self-drafter "
                         "(zero maps) so clients may submit with "
                         "spec_gamma > 0; implies --paged")
    ap.add_argument("--max-pending", type=int, default=64)
    ap.add_argument("--step-delay-s", type=float, default=0.0,
                    help="sleep after every engine step (testing knob: "
                         "widens the window for mid-stream cancellation "
                         "so smoke tests cannot race completion)")
    ap.add_argument("--no-retain-results", action="store_true",
                    help="drop each finished request from engine memory "
                         "once its stream has delivered it (long-running "
                         "deployments; the stats-window percentile path "
                         "keeps percentiles meaningful regardless)")
    ap.add_argument("--no-obs", action="store_true",
                    help="disable the observability layer (metrics op "
                         "then returns enabled=false); default on — "
                         "host-side only, no extra device dispatches")
    ap.add_argument("--trace-annotations", action="store_true",
                    help="wrap prefill/decode jit calls in jax.profiler."
                         "TraceAnnotation (lines device profiles up with "
                         "the host trace; needs obs enabled)")
    ap.add_argument("--trace-out", default=None, metavar="FILE",
                    help="export the request/step trace as a Chrome-trace/"
                         "Perfetto JSON file at shutdown (needs obs "
                         "enabled; open at https://ui.perfetto.dev)")
    args = ap.parse_args(argv)

    from repro.jitcache import enable_compile_cache
    enable_compile_cache()
    eng = _build_engine(args)
    step_cb = None
    if args.step_delay_s > 0:
        import time as _time
        step_cb = lambda _eng: _time.sleep(args.step_delay_s)  # noqa: E731
    aeng = AsyncEngine(eng, max_pending=args.max_pending,
                       retain_results=not args.no_retain_results,
                       step_cb=step_cb)
    srv = NBLServer(aeng, host=args.host, port=args.port)
    signal.signal(signal.SIGTERM, lambda *_: srv.request_stop())
    print(f"LISTENING {srv.port}", flush=True)
    try:
        srv.serve_forever()
    except KeyboardInterrupt:
        pass
    finally:
        try:
            srv.shutdown(drain=False)
        except RuntimeError as e:            # step loop died: report it
            print(f"server error: {e}", file=sys.stderr)
            return 1
        if args.trace_out and eng.obs is not None \
                and eng.obs.tracer is not None:
            n = eng.obs.tracer.export_chrome_trace(args.trace_out)
            print(f"trace: {n} events -> {args.trace_out}", file=sys.stderr)
    return 0


if __name__ == "__main__":
    sys.exit(main())
