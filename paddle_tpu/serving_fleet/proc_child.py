"""Subprocess serving replica — the child half of ``ProcReplica``.

Run as a PLAIN SCRIPT (never imported by the parent): it has no
package context until it bootstraps ``sys.path`` from its spec, which
keeps the pre-boot fault seams cheap — an injected exit-at-boot costs
milliseconds, not a paddle_tpu import.

Boot sequence:

1. read the spec (``PADDLE_TPU_PROC_SPEC``, JSON) + name/incarnation
   from argv; point the flight recorder at the per-incarnation dir the
   parent chose (a respawn must never clobber the carcass's
   post-mortem);
2. consult the boot fault seams with the INCARNATION as the seam step
   (``replica_exit_at_boot`` → exit now, nonzero;
   ``replica_slow_boot`` → sleep ``seconds`` before the heavy import,
   so a supervisor's boot gate sees a genuinely slow boot). The faults
   module is file-loaded (stdlib-only by contract) so this happens
   before any heavy import;
3. claim the wire: dup stdout onto a private fd and redirect fd 1 to
   stderr, so stray library prints can never interleave with frames;
4. heavy boot: import the builder from the spec, build the engine,
   ``warmup()`` the spec'd prefill buckets + decode program — the
   warm-boot contract: every compile this incarnation will ever need
   happens HERE, before the hello, so traffic after the boot gate
   runs under frozen compile counts;
5. serve: pump submit/cancel/drain ops from stdin (idempotent by
   fleet rid, same ledger discipline as ``InprocReplica``), step the
   engine, stream ``result`` + ``progress`` + throttled ``hb`` frames.

Shutdown hygiene (the round-14 satellite):

- SIGTERM installs a drain flag (handler set before the heavy boot):
  in-flight requests finish token-exactly, queued work bounces, every
  result is emitted, then a ``bye`` seals the stream and the process
  exits 0 — the subprocess analogue of the round-8
  checkpoint-and-exit contract;
- stdin EOF (the parent died) drains the same way after a short
  seeded-backoff retry (a transient empty read must not kill a
  healthy replica) — no orphan processes;
- the ``/metrics`` exporter port (when armed via ``metrics_port``) is
  released in ``finally``, so the next incarnation can bind it.
"""
from __future__ import annotations

import argparse
import importlib
import importlib.util
import json
import os
import signal
import sys
import threading
import time


def _load_faults_standalone():
    """File-load resilience/faults.py (stdlib-only by contract) so the
    boot seams fire before any heavy import."""
    path = os.path.join(os.path.dirname(os.path.abspath(__file__)),
                        os.pardir, "resilience", "faults.py")
    spec = importlib.util.spec_from_file_location("_proc_child_faults",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _resolve_builder(spec):
    b = spec.get("builder")
    if isinstance(b, dict):
        mspec = importlib.util.spec_from_file_location(
            "_proc_child_builder", b["path"])
        mod = importlib.util.module_from_spec(mspec)
        mspec.loader.exec_module(mod)
        return getattr(mod, b["fn"])
    modname, fn = str(b).split(":", 1)
    return getattr(importlib.import_module(modname), fn)


class _Child:
    def __init__(self, name, incarnation, spec, wire):
        self.name = name
        self.incarnation = incarnation
        self.spec = spec
        self.wire = wire
        self.poll_s = float(spec.get("poll_s", 0.002))
        self.heartbeat_s = float(spec.get("heartbeat_s", 0.02))
        self.drain_flag = threading.Event()
        self.engine = None
        self.exporter = None
        self._frame = None          # journal._frame, bound post-import
        self._ops = []
        self._ops_lock = threading.Lock()
        self._stdin_eof = False
        self._accepted = {}         # fleet rid -> engine rid
        self._rid_map = {}          # engine rid -> fleet rid
        self._precancel = set()
        self._progress_sent = {}    # fleet rid -> tokens emitted
        self._last_hb = 0.0
        self._rounds = 0
        self.state = "serving"
        self.warmed = False

    # -- wire --------------------------------------------------------------

    def emit(self, rec):
        self.wire.write(self._frame(rec))

    def heartbeat(self, force=False):
        now = time.monotonic()
        if not force and now - self._last_hb < self.heartbeat_s:
            return
        self._last_hb = now
        h = self.engine.health()
        qw = self.engine.registry.get("serve_queue_wait_seconds")
        p99 = qw.quantile(0.99) if qw is not None and qw.count else 0.0
        self.emit({
            "t": "hb", "replica": self.name, "state": self.state,
            "engine_state": h.get("state"), "ts": now,
            "round": self._rounds, "pid": os.getpid(),
            "warmed": self.warmed,
            "queued": h["queued"], "running": h["running"],
            "free_pages": h["free_pages"],
            "total_pages": h["total_pages"],
            "page_occupancy": h["page_occupancy"],
            "page_size": self.engine.page_size,
            "queue_wait_p99_s": round(float(p99 or 0.0), 6),
            "decode_tokens": h["decode_tokens"],
            "tenants_tracked": h.get("tenants_tracked", 0),
            "sampling": h.get("sampling"),
            "prefix_cache": h.get("prefix_cache"),
            "spec": h.get("spec"),
            "mem": h.get("mem"),
            "boot": h.get("boot"),
            "compile_counts": h["compile_counts"],
            "unexpected_retraces":
                self.engine.tracer.unexpected_retraces(),
            "metrics_port": None if self.exporter is None
            else self.exporter.port})

    # -- stdin op pump -----------------------------------------------------

    def _stdin_loop(self):
        """Read op frames off fd 0. A transient empty read retries on
        a seeded backoff (jitter_seed = incarnation, so each boot's
        schedule replays bit-identically); persistent EOF means the
        parent is gone — drain and exit rather than orphan."""
        from paddle_tpu.resilience.retry import backoff_schedule
        from paddle_tpu.serving_fleet.proc import FrameReader
        delays = backoff_schedule(3, base_delay=0.01, max_delay=0.1,
                                  jitter=0.5,
                                  jitter_seed=self.incarnation)
        fr = FrameReader()
        eofs = 0
        while True:
            try:
                data = os.read(0, 1 << 16)
            except OSError:
                data = b""
            if not data:
                if eofs < len(delays):
                    time.sleep(delays[eofs])
                    eofs += 1
                    continue
                self._stdin_eof = True
                self.drain_flag.set()
                return
            eofs = 0
            recs = fr.feed(data)
            if recs:
                with self._ops_lock:
                    self._ops.extend(recs)

    def _pump_ops(self):
        with self._ops_lock:
            ops, self._ops = self._ops, []
        for op in ops:
            t = op.get("t")
            if t == "submit":
                self._op_submit(op)
            elif t == "cancel":
                erid = self._accepted.get(op.get("rid"))
                if erid is not None:
                    self.engine.cancel(erid)
                else:
                    self._precancel.add(op.get("rid"))
            elif t == "drain":
                self.drain_flag.set()

    def _op_submit(self, op):
        frid = op["rid"]
        if frid in self._accepted:
            return     # idempotent: duplicate delivery dropped
        if frid in self._precancel:
            self._precancel.discard(frid)
            self.emit({"t": "result", "res": {
                "id": frid, "tokens": [], "status": "cancelled"}})
            return
        if self.state != "serving" or self.engine.state != "serving":
            self.emit({"t": "result", "res": {
                "id": frid, "tokens": [], "status": "bounced"}})
            return
        erid = self.engine.submit(
            op["prompt"], op["max_new"], op.get("eos"),
            priority=int(op.get("priority") or 0),
            deadline_ms=op.get("deadline_ms"),
            trace=op.get("trace"),
            tenant=op.get("tenant"))
        self._accepted[frid] = erid
        self._rid_map[erid] = frid

    # -- engine results / progress ----------------------------------------

    def _emit_engine(self, res):
        frid = self._rid_map.get(res["id"])
        if frid is None:
            return     # engine-local (warmup) — not fleet-owned
        if res.get("status") in ("ok", "expired", "cancelled"):
            # terminal: retire from the idempotency ledger (same
            # contract as InprocReplica._emit_engine — a later
            # re-submit of the rid is a fresh run, and the router's
            # resolved-rid dedup owns the at-least-once edge)
            self._accepted.pop(frid, None)
        self._progress_sent.pop(frid, None)
        out = {k: v for k, v in res.items() if k != "prompt"}
        self.emit({"t": "result", "res": dict(out, id=frid)})

    def _emit_progress(self):
        """Stream partial tokens for every live slot whose count grew:
        the channel the parent's export_inflight mirror — and so the
        router's failover harvest — is built from."""
        for ent in self.engine.export_inflight():
            frid = self._rid_map.get(ent["rid"])
            if frid is None or ent["queued"]:
                continue
            n = len(ent["tokens"])
            if n != self._progress_sent.get(frid):
                self._progress_sent[frid] = n
                self.emit({"t": "progress", "rid": frid,
                           "tokens": [int(x) for x in ent["tokens"]]})

    # -- main loop ---------------------------------------------------------

    def run(self):
        threading.Thread(target=self._stdin_loop, daemon=True,
                         name="proc-child-stdin").start()
        self.heartbeat(force=True)
        while True:
            self._rounds += 1
            self._pump_ops()
            if self.drain_flag.is_set():
                if self.engine.state == "serving":
                    self.engine.drain()
                self.state = "draining"
            if not self.engine.idle:
                for res in self.engine.step():
                    self._emit_engine(res)
                self._emit_progress()
            elif self.state == "draining":
                break
            else:
                time.sleep(self.poll_s)
            self.heartbeat()
        self.state = "drained"
        self.heartbeat(force=True)
        self.emit({"t": "bye", "state": "drained",
                   "reason": "eof" if self._stdin_eof else "drain"})


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--name", required=True)
    ap.add_argument("--incarnation", type=int, required=True)
    args = ap.parse_args(argv)
    spec = json.loads(os.environ.get("PADDLE_TPU_PROC_SPEC") or "{}")

    # boot fault seams FIRST (stdlib-only file-load; step=incarnation)
    pf = os.environ.get("PADDLE_TPU_PROC_FAULTS")
    if pf:
        os.environ["PADDLE_TPU_FAULTS"] = pf
    faults = _load_faults_standalone()
    faults.load_env()
    p = faults.pull("replica_exit_at_boot", args.incarnation)
    if p is not None:
        sys.exit(int(p.get("exit_code", 7)))
    faults.maybe_sleep("replica_slow_boot", args.incarnation)

    # drain flag armed before the heavy boot: a SIGTERM mid-compile
    # still drains at the first loop round
    drain_flag = threading.Event()
    signal.signal(signal.SIGTERM, lambda *_: drain_flag.set())

    # claim the wire: frames go to the dup'd fd; anything the heavy
    # imports print to "stdout" lands on stderr instead
    wire_fd = os.dup(1)
    os.dup2(2, 1)
    sys.stdout = sys.stderr
    wire = os.fdopen(wire_fd, "wb", buffering=0)

    for entry in reversed(spec.get("sys_path") or []):
        if entry not in sys.path:
            sys.path.insert(0, entry)
    if spec.get("force_cpu"):
        # read by jax when its backend initialises, so it must be set
        # before the builder imports jax
        os.environ["JAX_PLATFORMS"] = "cpu"

    t_boot = time.monotonic()
    builder = _resolve_builder(spec)
    engine = builder(**(spec.get("kwargs") or {}))
    from paddle_tpu.serving_fleet.journal import _frame

    child = _Child(args.name, args.incarnation, spec, wire)
    child._frame = _frame
    child.engine = engine
    child.drain_flag = drain_flag
    exporter = None
    try:
        if spec.get("metrics_port") is not None:
            exporter = engine.serve_metrics(
                port=int(spec["metrics_port"]))
            child.exporter = exporter
        # warm boot: the spec'd prefill buckets plus (always, unless
        # warmup=False) the decode program — heartbeats report the
        # ENGINE's warmed flag, never an unconditional claim, so the
        # supervisor's boot gate can't admit a cold replica. With an
        # artifact store configured (spec aot_dir / PADDLE_TPU_AOT_DIR)
        # the boot ladder prefers the AOT artifact — incarnation 1
        # traces and exports, every respawn after it boots from
        # serialized StableHLO in seconds; a torn/stale/corrupt
        # artifact falls back loudly (serve_aot_fallback_total) to the
        # traced path, so the gate can never admit a wrong program
        warm = spec.get("warmup")
        if warm is not False:
            from paddle_tpu.jit.serving_artifact import warm_boot
            warm_boot(engine, buckets=warm or (),
                      artifact_dir=spec.get("aot_dir"))
        child.warmed = bool(engine.warmed)
        child.emit({"t": "hello", "pid": os.getpid(),
                    "incarnation": args.incarnation,
                    "warmed": child.warmed,
                    "boot_s": round(time.monotonic() - t_boot, 6),
                    "boot": dict(engine.boot_info),
                    "compile_counts": engine.compile_counts()})
        child.run()
    finally:
        # release the exporter's port NOW — the next incarnation may
        # want to bind the same one
        if exporter is not None:
            exporter.close()
        try:
            wire.flush()
        except OSError:
            pass


if __name__ == "__main__":
    main()
