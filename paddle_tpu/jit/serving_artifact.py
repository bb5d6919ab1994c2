"""AOT serving artifacts — boot a warmed ServingEngine in seconds.

The autoscaler's reaction time is floored by replica boot, and replica
boot is floored by tracing: every respawn re-traces the full serving
program set (prefill buckets, the decode scan, the spec-verify
program) through Python before the warm-boot gate passes. This module
exports a warmed engine's programs via ``jax.export`` into a
**versioned, fingerprinted, crash-safe artifact**, and restores a
serving-ready engine from one WITHOUT tracing Python — so a scale-out
alert buys capacity in seconds, not compiles (ROADMAP item 3).

Artifact layout (a directory under the store root)::

    <root>/art-<fphash>-<n>/
        manifest.json        # fingerprint + per-blob sha256, atomic
        decode.stablehlo     # jax.export blobs, one per program site
        prefill_64.stablehlo
        ...
        COMPLETE             # written strictly LAST (io.atomic)

Crash-safety is the io.atomic discipline end to end: blobs land in a
``.stage-*`` sibling, every byte is fsynced, the directory is renamed
into place, and the COMPLETE marker is written strictly after — a
crash at ANY point leaves an unmarked (ignored) directory, never a
loadable half-artifact.

Robustness is the headline: the loader re-hashes every blob, diffs the
manifest fingerprint field-by-field against the live engine (model
config, dtype, page geometry, sampling, spec/prefix arming, jax/jaxlib
version, device kind), and on ANY mismatch raises ``ArtifactError``
with a machine-readable reason. ``warm_boot`` counts each fallback in
``serve_aot_fallback_total{reason}`` and falls back to the traced boot
path — never a wrong program, never a silent slow boot.

Token-exactness: the exported blob is the SAME jaxpr the traced boot
would compile (serialized StableHLO of the engine's own program
bodies), primed with the same trash-page synthetic arguments, with the
host RNG untouched — an artifact-booted engine generates
token-for-token what a traced-boot engine does, with zero post-load
Python traces.

Knobs (docs/observability.md): ``PADDLE_TPU_AOT_ARTIFACTS`` (kill
switch), ``PADDLE_TPU_AOT_DIR`` (store root), ``PADDLE_TPU_AOT_TTL_S``
(max artifact age).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import time

__all__ = ["ArtifactError", "artifact_fingerprint", "export_artifact",
           "load_artifact", "warm_boot"]

#: bump when the manifest/blob layout or the program calling
#: convention changes — a version mismatch is a stale fingerprint
FORMAT_VERSION = 1

_MANIFEST = "manifest.json"
_STAGE_PREFIX = ".stage-"
_ART_PREFIX = "art-"

#: every serving program donates the page pool at argument index 2
#: (the _counting contract); recorded per blob so the loader can't
#: drift from the export
_DONATE_PAGES = (2,)

#: fallback reasons — the serve_aot_fallback_total label vocabulary
REASONS = ("missing", "torn", "bad_manifest", "expired", "wrong_device",
           "stale_fingerprint", "bad_checksum", "deserialize_error",
           "install_error")


class ArtifactError(Exception):
    """A load-blocking artifact fault. `reason` is one of REASONS —
    the serve_aot_fallback_total{reason} label the caller counts."""

    def __init__(self, reason, detail=""):
        self.reason = reason
        self.detail = detail
        super().__init__(f"{reason}: {detail}" if detail else reason)


def _off(val, default="1"):
    return str(val if val is not None else default).lower() \
        in ("0", "false", "off")


def _cfg_dict(cfg):
    """The model config as a stable, JSON-safe dict (primitive fields
    only, sorted) — the model-architecture leg of the fingerprint."""
    out = {}
    for k, v in sorted(vars(cfg).items()):
        if isinstance(v, (str, int, float, bool)) or v is None:
            out[k] = v
    return out


def artifact_fingerprint(engine):
    """Everything that must match for a serialized program to be THE
    program this engine would trace: model architecture + dtype, page
    geometry, sampling, spec/prefix arming, jax/jaxlib version —
    plus the device (compared separately: a platform mismatch is
    `wrong_device`, not `stale_fingerprint`)."""
    import jax
    import jaxlib
    dev = jax.devices()[0]
    spec = engine._spec
    return {
        "format": FORMAT_VERSION,
        "model": type(engine.model).__name__,
        "config": _cfg_dict(engine.cfg),
        "cache_dtype": engine.cache_dtype,
        "page_size": engine.page_size,
        "max_slots": engine.max_slots,
        "max_seq_len": engine.max_seq_len,
        "num_pages": engine.num_pages,
        "steps_per_dispatch": engine.steps_per_dispatch,
        "pad_token_id": engine.pad_token_id,
        "use_flash": bool(engine.use_flash),
        # the pools' layout, which the programs' signatures carry
        "kv_heads_per_row": engine.kv_heads_per_row,
        # the weights' dtypes, which the programs' signatures carry
        "held_weights": {k: engine.held_weights[k]
                         for k in ("leaves", "dtype")},
        "donate": bool(engine.donate),
        "sampling": {"temperature": engine.temperature,
                     "top_k": engine.top_k,
                     "seed": engine.sampling_seed},
        "prefix": {"on": engine.prefix is not None,
                   "min_pages": None if engine.prefix is None
                   else engine.prefix.min_pages},
        "spec": {"armed": spec is not None,
                 "k": engine.spec_k if spec is not None else None,
                 "draft": spec.kind if spec is not None else None},
        "jax": jax.__version__,
        "jaxlib": jaxlib.__version__,
        "device": {"platform": dev.platform,
                   "kind": getattr(dev, "device_kind", dev.platform)},
    }


def _fp_hash(fp):
    blob = json.dumps(fp, sort_keys=True, allow_nan=False)
    return hashlib.sha256(blob.encode("utf-8")).hexdigest()[:16]


def _sites(engine):
    """The warmed program set, in install order."""
    out = [f"prefill_{n}" for n in sorted(engine._warmed_buckets)]
    out += [f"tail_prefill_{t}"
            for t in sorted(engine._warmed_tail_buckets)]
    if engine._warmed_decode:
        out.append("decode")
    if engine._warmed_spec:
        out.append("spec_verify")
    return out


def _candidates(root):
    """Marked artifact dirs under `root`, newest manifest first."""
    from ..io.atomic import has_marker
    found, unmarked = [], 0
    try:
        entries = sorted(os.listdir(root))
    except OSError:
        return [], 0
    for name in entries:
        path = os.path.join(root, name)
        if not (name.startswith(_ART_PREFIX) and os.path.isdir(path)):
            continue
        if not has_marker(path):
            unmarked += 1       # a torn (crashed-mid-export) artifact
            continue
        try:
            with open(os.path.join(path, _MANIFEST)) as f:
                manifest = json.load(f)
        except (OSError, json.JSONDecodeError, ValueError):
            found.append((0.0, path, None))     # marked but unreadable
            continue
        found.append((float(manifest.get("created_at") or 0.0),
                      path, manifest))
    found.sort(key=lambda x: (-x[0], x[1]))
    return found, unmarked


# -- export ------------------------------------------------------------------

def export_artifact(engine, root, prune=True):
    """Serialize the warmed engine's full program set into a fresh
    crash-safe artifact under `root`. Returns the artifact dir, or the
    existing one when an artifact with this exact fingerprint and a
    superset of the warmed sites is already published (idempotent —
    a fleet of replicas sharing a store exports once).

    Every program body is AOT-lowered via jax.export from the same raw
    fn + jit kwargs the traced boot compiles (engine._aot_programs),
    with the same warm-arg signatures — so the artifact IS the traced
    program, serialized. Staging + publish follow io.atomic: blobs are
    atomically written into a .stage sibling, fsynced, dir-renamed,
    marker strictly last (publish_dir)."""
    import jax
    from jax import export as jax_export
    from ..io.atomic import atomic_replace, publish_dir
    other = sorted(set(engine.cache_layers) - {"kv"})
    if other:
        what = {"latent": "serves from a latent paged cache",
                "conv_state": "has layers with a per-slot state",
                "delta_state": "has layers with a per-slot state"}
        raise ValueError(
            f"{type(engine.model).__name__} "
            f"{' and '.join(what[k] for k in other)}, which has no AOT "
            "artifact export yet (the artifact's fingerprint and warm "
            "signatures describe per-head K/V pools)")
    if not engine.warmed:
        raise RuntimeError("export_artifact needs a warmed engine — "
                           "warmup() first (export is a boot step)")
    fp = artifact_fingerprint(engine)
    fph = _fp_hash(fp)
    sites = _sites(engine)
    os.makedirs(root, exist_ok=True)
    cands, _ = _candidates(root)
    for _ts, path, manifest in cands:
        if manifest and manifest.get("fingerprint") == fp \
                and set(sites) <= set(manifest.get("blobs") or ()):
            return path
    staging = os.path.join(
        root, f"{_STAGE_PREFIX}{os.getpid()}-{fph}-{time.time_ns()}")
    os.makedirs(staging)
    blobs = {}
    for site in sites:
        fn, kw = engine._aot_programs[site]
        args = engine._warm_args(site)
        # one-shot AOT lowering of the raw program body — traced here,
        # at export time, never dispatched (the tracer-wrapped twin is
        # what serves); see tpulint baseline justification
        exp = jax_export.export(jax.jit(fn, **kw))(*args)
        blob = exp.serialize()
        fname = f"{site}.stablehlo"
        atomic_replace(os.path.join(staging, fname), blob, fsync=False)
        blobs[site] = {
            "file": fname,
            "bytes": len(blob),
            "sha256": hashlib.sha256(blob).hexdigest(),
            "donate_argnums": list(_DONATE_PAGES) if engine.donate
            else [],
        }
    manifest = {
        "version": FORMAT_VERSION,
        "created_at": time.time(),
        "fingerprint": fp,
        "warmed": {"buckets": sorted(engine._warmed_buckets),
                   "tail_buckets": sorted(engine._warmed_tail_buckets),
                   "decode": engine._warmed_decode,
                   "spec": engine._warmed_spec},
        "blobs": blobs,
    }
    atomic_replace(os.path.join(staging, _MANIFEST),
                   json.dumps(manifest, sort_keys=True, indent=1,
                              allow_nan=False),
                   fsync=False)
    final = os.path.join(root, f"{_ART_PREFIX}{fph}-{time.time_ns()}")
    publish_dir(staging, final)
    from ..observability import flightrec
    flightrec.note("serve_aot_export", artifact=os.path.basename(final),
                   sites=sites, fingerprint_hash=fph)
    if prune:
        _prune(root, keep=final)
    return final


def _prune(root, keep, stage_ttl_s=86400.0):
    """Store hygiene, best-effort: drop superseded MARKED artifacts
    (the loader only ever reads the newest) and stage leftovers older
    than `stage_ttl_s` (a concurrent exporter's live staging dir is
    younger and survives)."""
    from ..io.atomic import has_marker
    now = time.time()
    try:
        entries = sorted(os.listdir(root))
    except OSError:
        return
    for name in entries:
        path = os.path.join(root, name)
        try:
            if name.startswith(_STAGE_PREFIX):
                if now - os.path.getmtime(path) > stage_ttl_s:
                    shutil.rmtree(path, ignore_errors=True)
            elif name.startswith(_ART_PREFIX) and os.path.isdir(path) \
                    and path != keep and has_marker(path):
                shutil.rmtree(path, ignore_errors=True)
        except OSError:
            continue


# -- load --------------------------------------------------------------------

def _diff_fingerprint(want, got):
    """Top-level fingerprint fields that disagree (sorted)."""
    keys = set(want) | set(got if isinstance(got, dict) else {})
    keys.discard("device")
    return sorted(k for k in keys
                  if (got or {}).get(k) != want.get(k))


def load_artifact(engine, root, ttl_s=None, buckets=()):
    """Restore a serving-ready, warmed engine from the newest artifact
    under `root` WITHOUT tracing Python: every blob is re-hashed
    against the manifest, the fingerprint is diffed field-by-field
    against the live engine, and only then are the deserialized
    programs installed, primed once with the same trash-page synthetic
    arguments warmup() uses, and the _warmed_* flags flipped.

    Raises ArtifactError(reason) on ANY fault — the engine is left
    exactly as found (installation is all-or-nothing: deserialization
    and platform checks happen before the first install; an install-
    time fault rolls the program table back to build-on-first-use).
    Returns a boot-info dict (artifact name, sites, topped-up
    buckets)."""
    import jax
    from jax import export as jax_export
    if engine._state == "closed":
        raise RuntimeError("ServingEngine is closed")
    if not os.path.isdir(root):
        raise ArtifactError("missing", f"no artifact store at {root}")
    cands, unmarked = _candidates(root)
    if not cands:
        if unmarked:
            raise ArtifactError(
                "torn", f"{unmarked} unmarked artifact dir(s) under "
                        f"{root} (crash mid-export) and no complete one")
        raise ArtifactError("missing", f"no published artifact in {root}")
    created, path, manifest = cands[0]
    name = os.path.basename(path)
    if manifest is None:
        raise ArtifactError("bad_manifest",
                            f"{name}: unreadable manifest.json")
    if manifest.get("version") != FORMAT_VERSION:
        raise ArtifactError(
            "stale_fingerprint",
            f"{name}: format v{manifest.get('version')} != "
            f"v{FORMAT_VERSION}")
    if ttl_s is not None and time.time() - created > float(ttl_s):
        raise ArtifactError(
            "expired", f"{name}: {time.time() - created:.0f}s old "
                       f"> ttl {float(ttl_s):.0f}s")
    want = artifact_fingerprint(engine)
    got = manifest.get("fingerprint") or {}
    if got.get("device") != want["device"]:
        raise ArtifactError(
            "wrong_device",
            f"{name}: built for {got.get('device')}, "
            f"running on {want['device']}")
    bad = _diff_fingerprint(want, got)
    if bad:
        raise ArtifactError(
            "stale_fingerprint", f"{name}: mismatched {', '.join(bad)}")

    # verify + deserialize EVERY blob before touching the engine
    blobs = manifest.get("blobs") or {}
    platform = jax.devices()[0].platform
    exps = {}
    blob_bytes = 0
    for site, meta in sorted(blobs.items()):
        bpath = os.path.join(path, meta.get("file") or "")
        try:
            with open(bpath, "rb") as f:
                raw = f.read()
        except OSError as e:
            raise ArtifactError("torn",
                                f"{name}: blob {site} unreadable "
                                f"({e})") from e
        digest = hashlib.sha256(raw).hexdigest()
        if digest != meta.get("sha256"):
            raise ArtifactError(
                "bad_checksum",
                f"{name}: blob {site} sha256 {digest[:12]}… != "
                f"manifest {str(meta.get('sha256'))[:12]}…")
        try:
            exp = jax_export.deserialize(raw)
        except Exception as e:  # noqa: BLE001 — any decode fault
            raise ArtifactError(
                "deserialize_error", f"{name}: blob {site}: {e}") from e
        if platform not in exp.platforms:
            raise ArtifactError(
                "wrong_device",
                f"{name}: blob {site} lowered for {exp.platforms}, "
                f"running on {platform}")
        exps[site] = (exp, tuple(meta.get("donate_argnums") or ()))
        blob_bytes += len(raw)

    warmed = manifest.get("warmed") or {}
    try:
        for site, (exp, donate) in sorted(exps.items()):
            kw = {"donate_argnums": donate} \
                if (engine.donate and donate) else {}
            # through the engine's own RecompileTracer, so the one
            # wrapper trace of exp.call (NOT of the Python model)
            # lands in compile_counts like any boot compile, and a
            # steady-state retrace would still trip the
            # zero-recompile accounting. introspect=False: no
            # AOT-replay double compile at boot.
            call = engine.tracer.jit(site, exp.call, introspect=False,
                                     **kw)
            engine._install_aot_program(site, call)
            engine._prime(site, call)
        engine._warmed_buckets.update(warmed.get("buckets") or ())
        engine._warmed_tail_buckets.update(
            warmed.get("tail_buckets") or ())
        engine._warmed_decode |= bool(warmed.get("decode"))
        if engine._spec is not None and warmed.get("spec"):
            engine._warmed_spec = True
        norm = sorted(engine._warmed_buckets)
        if engine.prefix is not None and norm:
            engine._warm_eager_ladder(norm)
        if engine._spec is not None:
            # the proposer's own programs (draft prefill/propose scan
            # for a model draft; nothing for ngram) are tiny — they
            # warm live at load, inside the boot budget
            engine._spec.warmup(engine, norm)
        # traced top-up for anything the caller asked for that the
        # artifact doesn't carry (e.g. a new bucket after a routing
        # change) — loud in compile_counts, never a wrong program
        missing = sorted({engine._bucket_for(n) for n in buckets}
                         - engine._warmed_buckets)
        if missing or not engine._warmed_decode:
            engine.warmup(buckets=missing)
    except ArtifactError:
        raise
    except Exception as e:  # noqa: BLE001 — any install/prime fault
        # roll the program table back to build-on-first-use so a
        # half-installed set can never serve
        engine._decode_fn = engine._build_decode_fn()
        engine._prefill_fns.clear()
        engine._tail_prefill_fns.clear()
        if engine._spec is not None:
            engine._spec_verify_fn = engine._build_spec_verify_fn()
        engine._warmed_buckets.clear()
        engine._warmed_tail_buckets.clear()
        engine._warmed_decode = False
        engine._warmed_spec = False
        raise ArtifactError("install_error", str(e)) from e
    info = {"artifact": name, "sites": sorted(exps),
            "topped_up": missing}
    if getattr(engine, "ledger", None) is not None:
        # artifact restore seam: the deserialized executables' blob
        # bytes land in the ledger's "other" segment (level, not a
        # tracked token — a reload replaces, never accumulates)
        engine.ledger.set_level("other", blob_bytes,
                                label="serving_artifact")
    from ..observability import flightrec
    flightrec.note("serve_aot_load", **info)
    return info


# -- the spawn-path boot ladder ----------------------------------------------

def _own_counter(engine, name, help, labels=None):
    m = engine.registry.counter(
        name, help=help, **({"labels": labels} if labels else {}))
    if m not in engine._own_series:
        engine._own_series.append(m)
    return m


def warm_boot(engine, buckets=(), artifact_dir=None, export=None,
              ttl_s=None):
    """THE fleet spawn path: prefer-artifact, fall back loudly, export
    after a traced boot so the NEXT spawn is fast.

    1. resolve the store root (`artifact_dir`, else PADDLE_TPU_AOT_DIR)
       and the kill switch (PADDLE_TPU_AOT_ARTIFACTS, default on); no
       root or switched off -> plain traced warmup, byte-identical to
       the pre-artifact boot path;
    2. try load_artifact: success is an AOT boot (zero Python traces);
    3. ANY ArtifactError increments
       serve_aot_fallback_total{reason} — the loud part — and falls
       back to traced warmup: never a wrong program, never a silent
       slow boot;
    4. after a traced boot (fallback or cold store), export the warmed
       program set (best-effort, counted on failure) so respawns and
       scale-outs board the fast path.

    Stamps engine.boot_info (mode aot|traced, boot_s, artifact) —
    heartbeats carry it to the supervisor/autoscaler and fleet_top's
    BOOT column. Returns the boot_info dict."""
    t0 = time.monotonic()
    root = artifact_dir if artifact_dir is not None \
        else os.environ.get("PADDLE_TPU_AOT_DIR")
    enabled = root and not _off(
        os.environ.get("PADDLE_TPU_AOT_ARTIFACTS"))
    if ttl_s is None:
        env_ttl = os.environ.get("PADDLE_TPU_AOT_TTL_S")
        ttl_s = float(env_ttl) if env_ttl else None
    if not enabled:
        engine.warmup(buckets=buckets)
        engine.boot_info.update(
            mode="traced", boot_s=round(time.monotonic() - t0, 6),
            artifact=None)
        return dict(engine.boot_info)
    mode, artifact = "traced", None
    try:
        info = load_artifact(engine, root, ttl_s=ttl_s,
                             buckets=buckets)
        mode, artifact = "aot", info["artifact"]
        _own_counter(engine, "serve_aot_loads_total",
                     help="successful artifact boots").inc()
    except ArtifactError as e:
        _own_counter(engine, "serve_aot_fallback_total",
                     help="artifact-boot attempts that fell back to "
                          "the traced path, by reason (torn/stale/"
                          "corrupt artifacts are counted here, never "
                          "silently slow)",
                     labels={"reason": e.reason}).inc()
        from ..observability import flightrec
        flightrec.note("serve_aot_fallback", reason=e.reason,
                       detail=e.detail)
        engine.warmup(buckets=buckets)
        if export is None or export:
            try:
                artifact = os.path.basename(
                    export_artifact(engine, root))
            except Exception as ex:  # noqa: BLE001 — export is an
                #                      optimization; boot must survive
                _own_counter(
                    engine, "serve_aot_export_failures_total",
                    help="artifact exports that failed (boot "
                         "unaffected; the next spawn re-traces)").inc()
                flightrec.note("serve_aot_export_failed",
                               error=str(ex))
    engine.boot_info.update(
        mode=mode, boot_s=round(time.monotonic() - t0, 6),
        artifact=artifact)
    return dict(engine.boot_info)
