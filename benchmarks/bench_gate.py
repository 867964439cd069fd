"""Perf gate — local-kernel x engine-family sweep with a schema-checked
JSON artifact (DESIGN.md §7).

The paper's headline result (Fig 4.2 / §3.2.1) is that eliminating the
materialized random-number buffer is the step that turns the update loop
bandwidth-bound: our ``fused`` local kernel is exactly that move, now
available inside the sharded engines' shard_map regions. This module is
the CI-tracked evidence: it sweeps every local kernel {jnp, pallas,
fused} across every engine family {sublattice, sharded, sharded_pod} and
writes ``BENCH_kernels.json`` — the artifact the ``perf-smoke`` CI job
validates and uploads every run, seeding the perf trajectory.

Stdout keeps the common benchmark contract (``name,us_per_call,derived``
CSV rows, or one JSON object per row under ``BENCH_JSON=1``); the richer
per-row fields land in the artifact. Both formats are validated by the
functions below (also exposed as ``--validate FILE...`` for CI):

* a *row* must carry ``name`` (non-empty str), ``us_per_call`` (number
  > 0) and ``derived`` (str);
* the *document* must carry ``schema == "escg-bench-kernels/v5"``,
  ``backend``/``devices``/``smoke`` metadata and a non-empty ``rows``
  list whose entries extend the row schema with ``family``,
  ``scenario`` (the registered scenario-layer preset the cell ran,
  DESIGN.md §10), ``local_kernel``, ``engine``, ``backend`` (new in v3
  — rows are self-identifying so history lines compare across
  runners), ``observables`` (bool, new in v4 — whether the chunk ran
  the on-device observable pipeline of DESIGN.md §11), ``lattice``
  ([H, W]), ``mcs``, ``n_trials`` (the REQUESTED trial count; 0 for
  the single-lattice families), ``n_pad`` (the padded batch that
  actually ran — v2 conflated the two as ``trials`` and normalized
  throughput over padding), ``updates_per_s`` (normalized over
  *useful* updates: ``mcs * n_cells * max(n_trials, 1)``, never the
  padded batch) and ``timing`` (per-call stats: ``median_us`` /
  ``mean_us`` / ``min_us`` / ``max_us`` / ``n``) — and whose rows must
  cover ALL three local kernels AND all three swept scenarios {park3,
  zhong_density, nspecies5} (the acceptance criterion; a sweep that
  silently drops one fails validation, not review).

New in v5: the document additionally carries one family-``serve``
derived row — the serving layer (DESIGN.md §12) replays the committed
smoke trace (``examples/traces/smoke.jsonl``) through an in-process
``ScenarioServer`` and records requests/s, useful-update throughput
and the compiled-engine cache counters (``validate_serve_row``; the
row rides the same ``--history`` trajectory as the kernel rows, and a
v5 document without one fails validation).

The v4 sweep records *observable overhead* as paired rows: every
engine family runs park3/jnp twice, once with the observable pipeline
off (``observables: false``) and once streaming the park3 observable
set into the device ring buffer (``observables: true``, name suffix
``_obs``); the on-row's ``derived`` string carries the measured
overhead versus its off twin. ISSUE 9's acceptance criterion is that
this overhead stays within ~10% in the smoke sweep.

Beyond schema validation the gate now *bites*: ``--compare BASELINE``
diffs the fresh sweep against a committed document and exits non-zero
when any matching ``(family, scenario, local_kernel, backend,
observables)`` row regresses ``updates_per_s`` by more than
``--regressionThreshold``
(fraction; CI uses 0.75 — generous because CPU-runner jitter is real,
but a genuine order-of-magnitude regression still fails the build).
``--history FILE`` appends the full document as one JSONL line (the
perf trajectory artifact CI uploads); ``--candidate FILE`` compares an
existing document instead of re-benchmarking.

Run:  [ESCG_BENCH_SMOKE=1] PYTHONPATH=src python -m benchmarks.bench_gate \
          [--out BENCH_kernels.json] [--compare BENCH_kernels.json] \
          [--regressionThreshold 0.75] [--history BENCH_history.jsonl]
      PYTHONPATH=src python -m benchmarks.bench_gate --validate FILE...
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
from typing import List, Optional

# must happen before the first jax import anywhere in the process
if os.environ.get("ESCG_FAKE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        os.environ.get("XLA_FLAGS", "")
        + " --xla_force_host_platform_device_count="
        + os.environ["ESCG_FAKE_DEVICES"])

SCHEMA = "escg-bench-kernels/v5"
SCHEMA_V4 = "escg-bench-kernels/v4"
SCHEMA_V3 = "escg-bench-kernels/v3"
# history lines from older gate versions stay valid against the schema
# they were written under (the trajectory spans schema bumps); fresh
# documents and compare baselines must carry the CURRENT schema
KNOWN_SCHEMAS = (SCHEMA_V3, SCHEMA_V4, SCHEMA)
# v5: the document additionally carries >= 1 family-"serve" derived row —
# serving throughput under the smoke trace (requests/s and Mupd/s from
# repro.serve.loadgen.gate_row) riding the same --history trajectory
SERVE_FAMILY = "serve"
FAMILIES = ("sublattice", "sharded", "sharded_pod")
LOCAL_KERNELS = ("jnp", "pallas", "fused")
# scenario-layer sweep (v2): park3 carries the full kernel x family grid;
# the other study presets pin the jnp kernel per family — the artifact
# must cover ALL of both tuples (validate_gate_document)
SCENARIOS = ("park3", "zhong_density", "nspecies5")
# the sublattice family is the single-device engine of each kernel lineage
SINGLE_ENGINE = {"jnp": "sublattice", "pallas": "pallas",
                 "fused": "pallas_fused"}


# ------------------------------ validation -------------------------------- #
# Hand-rolled (no jsonschema dependency); returns a list of human-readable
# errors, empty when valid. CI fails on any non-empty list.

def _check(obj: dict, field: str, types, errors: List[str],
           ctx: str) -> None:
    if field not in obj:
        errors.append(f"{ctx}: missing field {field!r}")
    elif not isinstance(obj[field], types):
        errors.append(f"{ctx}: field {field!r} has type "
                      f"{type(obj[field]).__name__}, want {types}")


def validate_row(obj, ctx: str = "row") -> List[str]:
    """The stdout BENCH_JSON row contract every benchmark module emits."""
    if not isinstance(obj, dict):
        return [f"{ctx}: not a JSON object"]
    errors: List[str] = []
    _check(obj, "name", str, errors, ctx)
    _check(obj, "us_per_call", (int, float), errors, ctx)
    _check(obj, "derived", str, errors, ctx)
    if not errors:
        if not obj["name"]:
            errors.append(f"{ctx}: empty name")
        if isinstance(obj["us_per_call"], bool) or obj["us_per_call"] <= 0:
            errors.append(f"{ctx}: us_per_call must be a positive number, "
                          f"got {obj['us_per_call']!r}")
    return errors


TIMING_FIELDS = ("median_us", "mean_us", "min_us", "max_us", "n")


def validate_serve_row(obj, ctx: str = "row") -> List[str]:
    """A family-``serve`` derived row (v5): serving throughput of a trace
    replay, not a kernel timing — no lattice/timing block, instead the
    request counters the serve-smoke CI job gates on."""
    errors = validate_row(obj, ctx)
    if not isinstance(obj, dict):
        return errors
    for fld in ("scenario", "local_kernel", "engine", "backend"):
        _check(obj, fld, str, errors, ctx)
    _check(obj, "observables", bool, errors, ctx)
    _check(obj, "n_requests", int, errors, ctx)
    _check(obj, "requests_per_s", (int, float), errors, ctx)
    _check(obj, "updates_per_s", (int, float), errors, ctx)
    _check(obj, "cache_hits", int, errors, ctx)
    _check(obj, "cache_misses", int, errors, ctx)
    _check(obj, "dropped", int, errors, ctx)
    if errors:
        return errors
    if obj["n_requests"] < 1:
        errors.append(f"{ctx}: serve row n_requests must be >= 1")
    if obj["requests_per_s"] <= 0 or obj["updates_per_s"] <= 0:
        errors.append(f"{ctx}: serve row throughput must be positive")
    if obj["cache_hits"] < 0 or obj["cache_misses"] < 0:
        errors.append(f"{ctx}: serve row cache counters must be >= 0")
    if obj["dropped"] != 0:
        errors.append(f"{ctx}: serve row dropped={obj['dropped']} — every "
                      "admitted request must be answered")
    return errors


def validate_gate_row(obj, ctx: str = "row",
                      schema: str = SCHEMA) -> List[str]:
    if isinstance(obj, dict) and obj.get("family") == SERVE_FAMILY:
        if schema in (SCHEMA_V3, SCHEMA_V4):
            return [f"{ctx}: family 'serve' rows require schema {SCHEMA} "
                    f"(document declares {schema})"]
        return validate_serve_row(obj, ctx)
    errors = validate_row(obj, ctx)
    if not isinstance(obj, dict):
        return errors
    _check(obj, "family", str, errors, ctx)
    _check(obj, "scenario", str, errors, ctx)
    _check(obj, "local_kernel", str, errors, ctx)
    _check(obj, "engine", str, errors, ctx)
    _check(obj, "backend", str, errors, ctx)
    if schema != SCHEMA_V3:                 # observables is new in v4
        _check(obj, "observables", bool, errors, ctx)
    _check(obj, "lattice", list, errors, ctx)
    _check(obj, "mcs", int, errors, ctx)
    _check(obj, "n_trials", int, errors, ctx)
    _check(obj, "n_pad", int, errors, ctx)
    _check(obj, "updates_per_s", (int, float), errors, ctx)
    _check(obj, "timing", dict, errors, ctx)
    if errors:
        return errors
    if obj["family"] not in FAMILIES:
        errors.append(f"{ctx}: family {obj['family']!r} not in {FAMILIES}")
    if obj["scenario"] not in SCENARIOS:
        errors.append(f"{ctx}: scenario {obj['scenario']!r} not in "
                      f"{SCENARIOS}")
    if obj["local_kernel"] not in LOCAL_KERNELS:
        errors.append(f"{ctx}: local_kernel {obj['local_kernel']!r} not in "
                      f"{LOCAL_KERNELS}")
    if (len(obj["lattice"]) != 2
            or not all(isinstance(v, int) and v > 0
                       for v in obj["lattice"])):
        errors.append(f"{ctx}: lattice must be [H, W] positive ints, got "
                      f"{obj['lattice']!r}")
    if obj["mcs"] < 0 or obj["n_trials"] < 0:
        errors.append(f"{ctx}: mcs/n_trials must be >= 0")
    if obj["n_pad"] < obj["n_trials"]:
        errors.append(f"{ctx}: n_pad ({obj['n_pad']}) < n_trials "
                      f"({obj['n_trials']}) — padding can only grow the "
                      "batch")
    if obj["updates_per_s"] < 0:
        errors.append(f"{ctx}: updates_per_s must be >= 0")
    for fld in TIMING_FIELDS:
        v = obj["timing"].get(fld)
        if not isinstance(v, (int, float)) or isinstance(v, bool) or v <= 0:
            errors.append(f"{ctx}: timing[{fld!r}] must be a positive "
                          f"number, got {v!r}")
    if not errors and obj["timing"]["min_us"] > obj["timing"]["max_us"]:
        errors.append(f"{ctx}: timing min_us > max_us")
    return errors


def validate_gate_document(doc, accept=(SCHEMA,)) -> List[str]:
    """The BENCH_kernels.json artifact the perf-smoke CI job uploads.

    ``accept`` is the set of schema versions tolerated: fresh documents
    and compare baselines require the current schema (the default);
    ``validate_file`` passes KNOWN_SCHEMAS for history lines so older
    trajectory entries keep validating against the schema they declare."""
    if not isinstance(doc, dict):
        return ["document: not a JSON object"]
    errors: List[str] = []
    schema = doc.get("schema")
    if schema not in accept:
        errors.append(f"document: schema {schema!r} not in {accept!r}")
        schema = SCHEMA
    _check(doc, "backend", str, errors, "document")
    _check(doc, "devices", int, errors, "document")
    _check(doc, "smoke", bool, errors, "document")
    _check(doc, "rows", list, errors, "document")
    if errors:
        return errors
    if doc["devices"] < 1:
        errors.append("document: devices must be >= 1")
    if not doc["rows"]:
        errors.append("document: rows is empty")
    for i, row in enumerate(doc["rows"]):
        errors.extend(validate_gate_row(row, ctx=f"rows[{i}]",
                                        schema=schema))
    for fld, want in (("local_kernel", LOCAL_KERNELS),
                      ("scenario", SCENARIOS)):
        covered = {r.get(fld) for r in doc["rows"] if isinstance(r, dict)}
        missing = set(want) - covered
        if missing:
            errors.append(f"document: rows cover {fld}s {sorted(covered)} "
                          f"— missing {sorted(missing)} (all of {want} "
                          "are required)")
    if schema == SCHEMA and not any(
            isinstance(r, dict) and r.get("family") == SERVE_FAMILY
            for r in doc["rows"]):
        errors.append(f"document: {SCHEMA} requires at least one "
                      "family-'serve' derived row (serving throughput "
                      "under the smoke trace)")
    return errors


def validate_file(path: str) -> List[str]:
    """Validate a BENCH_kernels.json document, a BENCH_history.jsonl
    trajectory (one gate *document* per line), or a BENCH_JSON row stream
    (one row object per line; blank and '#' lines are ignored). History
    and row lines may be mixed — each line is dispatched on the presence
    of a ``schema`` field."""
    with open(path) as f:
        text = f.read()
    try:
        doc = json.loads(text)
    except json.JSONDecodeError:
        doc = None
    if isinstance(doc, dict) and "schema" in doc:
        return [f"{path}: {e}" for e in validate_gate_document(doc)]
    errors: List[str] = []
    rows = 0
    for ln_no, line in enumerate(text.splitlines(), 1):
        line = line.strip()
        if not line or line.startswith("#"):
            continue
        try:
            obj = json.loads(line)
        except json.JSONDecodeError as e:
            errors.append(f"{path}:{ln_no}: not JSON ({e})")
            continue
        rows += 1
        if isinstance(obj, dict) and "schema" in obj:
            errors.extend(f"{path}:{ln_no}: {e}"
                          for e in validate_gate_document(
                              obj, accept=KNOWN_SCHEMAS))
        else:
            errors.extend(validate_row(obj, ctx=f"{path}:{ln_no}"))
    if rows == 0:
        errors.append(f"{path}: no benchmark rows found")
    return errors


# ------------------------- trajectory gating ------------------------------ #

def row_key(row: dict):
    """The identity a perf trajectory tracks: what ran and where, never
    how fast. Lattice size / MCS / trial counts are deliberately NOT part
    of the key — those change with sweep sizing, and the smoke guard in
    ``compare_documents`` keeps apples with apples. ``observables`` IS
    part of the key (v4): an obs-on row is a different workload than its
    off twin and must only ever gate against another obs-on row."""
    return (row.get("family"), row.get("scenario"),
            row.get("local_kernel"), row.get("backend"),
            bool(row.get("observables")))


def compare_documents(candidate: dict, baseline: dict,
                      threshold: float) -> List[str]:
    """Regression diff of two gate documents; returns human-readable
    failures (empty = gate passes).

    A matching ``(family, scenario, local_kernel, backend, observables)``
    row regresses
    when ``candidate.updates_per_s < baseline.updates_per_s * (1 -
    threshold)``. Documents with different ``smoke`` flags are
    incomparable (different sweep sizes) and compare clean with a note;
    an invalid baseline fails loudly — a gate diffing against garbage
    would silently pass forever."""
    if not 0.0 < threshold < 1.0:
        return [f"regression threshold must be in (0, 1), got {threshold}"]
    base_errors = validate_gate_document(baseline)
    if base_errors:
        return [f"baseline invalid: {e}" for e in base_errors]
    if bool(candidate.get("smoke")) != bool(baseline.get("smoke")):
        print("# compare: smoke flags differ (candidate "
              f"{candidate.get('smoke')} vs baseline "
              f"{baseline.get('smoke')}) — sweeps incomparable, skipping",
              file=sys.stderr)
        return []
    base_rows = {row_key(r): r for r in baseline["rows"]}
    failures: List[str] = []
    matched = 0
    for row in candidate.get("rows", []):
        base = base_rows.get(row_key(row))
        if base is None:
            continue
        matched += 1
        floor = base["updates_per_s"] * (1.0 - threshold)
        if row["updates_per_s"] < floor:
            failures.append(
                f"{row['name']}: {row['updates_per_s']:.1f} upd/s < "
                f"{floor:.1f} (baseline {base['updates_per_s']:.1f}, "
                f"threshold {threshold:.0%})")
    if matched == 0:
        failures.append(
            "no candidate row matches any baseline (family, scenario, "
            "local_kernel, backend, observables) key — the gate compared "
            "nothing")
    return failures


def append_history(doc: dict, path: str) -> None:
    """Append the full gate document as one JSONL line — the perf
    trajectory artifact (validated by ``validate_file``; CI uploads it
    every perf-smoke run)."""
    with open(path, "a") as f:
        f.write(json.dumps(doc, separators=(",", ":")) + "\n")


# -------------------------------- sweep ----------------------------------- #

# the obs-on rows stream the park3 scenario observable set (DESIGN.md
# §11): per-species densities plus the interface-length order parameter —
# the pairing the overhead acceptance criterion is defined over
OBS_SET = ("densities", "interface_length")


def _gate_config(family: str, kernel: str, scenario: str,
                 observables: bool = False):
    """(EscgParams, Scenario) for one sweep cell — a scenario-layer
    composition: physics from the registered preset (mobility pinned to
    1e-4 and empty to 0.1 so occupancy is comparable across studies),
    engine/run from the cell. ``observables=True`` turns on the
    device-ring observable pipeline (OBS_SET) for the overhead rows."""
    from repro.core.scenarios import (EngineConfig, RunConfig, compose,
                                      make_scenario)
    from .common import smoke
    L = smoke(32, 64)
    h = smoke(16, 64)
    if family == "sublattice":
        engine, lk = SINGLE_ENGINE[kernel], "jnp"   # knob ignored
    else:
        engine, lk = family, kernel
    sc = make_scenario(scenario).replace(mobility=1e-4, empty=0.1)
    p = compose(sc, EngineConfig(engine=engine, local_kernel=lk,
                                 tile=(8, 16)),
                RunConfig(length=L, height=h, seed=0,
                          observables=OBS_SET if observables else ()))
    return p, sc


def _bench_combo(family: str, kernel: str, scenario: str, mcs: int,
                 trials: int, observables: bool = False) -> dict:
    """Per-call timing stats of one jitted chunk (compile excluded, like
    fig4_3): a simulate() chunk for the one-lattice families, a
    run_trials chunk for the composed family. With ``observables=True``
    the chunk is the observable-pipeline variant (DESIGN.md §11): same
    dynamics, but every MCS also banks an OBS_SET row into the
    device-resident ring buffer — the timing delta against the off twin
    IS the observable overhead the gate records.

    Throughput normalization (the v2 bug this schema fixes): the
    composed family pads the trial batch to the pod width, so the kernel
    *runs* ``n_pad`` lattices — but ``updates_per_s`` counts only the
    ``n_trials`` REQUESTED lattices. Normalizing over padding made the
    same workload look faster on wider pods (free throughput from wasted
    work); both counts now land in the row so either view is
    recoverable."""
    import jax
    import jax.numpy as jnp

    from repro.core import engines
    from repro.core import observables as obs_mod
    from repro.core.lattice import init_grid
    from .common import time_stats

    p, sc = _gate_config(family, kernel, scenario, observables=observables)
    dom = jnp.asarray(sc.dominance(), jnp.float32)
    built = engines.build(p, dom)
    if family == "sharded_pod":
        from repro.core.trials import (build_trial_chunk,
                                       build_trial_obs_chunk, pad_trials,
                                       trial_grids_and_keys)
        n_trials = trials
        n_pad = pad_trials(n_trials, built.pod_width)
        grids, keys = trial_grids_and_keys(
            p, jax.random.PRNGKey(0), n_pad, sharding=built.key_sharding,
            grid_sharding=built.batch_sharding)
        if observables:
            chunk, pipe = build_trial_obs_chunk(p, dom, built=built)
            ring, pos = obs_mod.ring_init(
                obs_mod.ring_capacity(p, mcs), (n_pad, pipe.width))
            stats = time_stats(lambda: chunk(grids, keys, ring, pos, mcs),
                               warmup=2, iters=9)
        else:
            chunk = build_trial_chunk(p, dom, built=built)
            stats = time_stats(lambda: chunk(grids, keys, mcs),
                               warmup=2, iters=9)
        n_upd = mcs * p.n_cells * n_trials
    else:
        from repro.core.simulation import build_chunk_fn, build_obs_chunk_fn
        grid = init_grid(jax.random.PRNGKey(0), p.height, p.length,
                         p.species, p.empty)
        if built.grid_sharding is not None:
            grid = jax.device_put(grid, built.grid_sharding)
        if observables:
            chunk, pipe = build_obs_chunk_fn(p, dom, built=built)
            ring, pos = obs_mod.ring_init(
                obs_mod.ring_capacity(p, mcs), (pipe.width,))
            stats = time_stats(
                lambda: chunk(grid, jax.random.PRNGKey(1), ring, pos, mcs),
                warmup=2, iters=9)
        else:
            chunk = build_chunk_fn(p, dom, one_mcs=built.one_mcs)
            stats = time_stats(
                lambda: chunk(grid, jax.random.PRNGKey(1), mcs),
                warmup=2, iters=9)
        n_upd = mcs * p.n_cells
        n_trials = n_pad = 0
    t = stats["median_us"] / 1e6
    upd_s = n_upd / t
    suffix = "_obs" if observables else ""
    return {
        "name": f"kernelgate_{scenario}_{family}_{kernel}{suffix}",
        "us_per_call": stats["median_us"],
        "derived": f"{upd_s / 1e6:.3f} Mupd/s engine={p.engine} "
                   f"scenario={scenario}",
        "family": family,
        "scenario": scenario,
        "local_kernel": kernel,
        "engine": p.engine,
        "backend": jax.default_backend(),
        "observables": bool(observables),
        "lattice": [p.height, p.length],
        "mcs": mcs,
        "n_trials": n_trials,
        "n_pad": n_pad,
        "updates_per_s": round(upd_s, 1),
        "timing": stats,
    }


SMOKE_TRACE = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "examples", "traces", "smoke.jsonl")


def _serve_row() -> dict:
    """The v5 ``serve_throughput`` derived row: replay the committed
    smoke trace (synthetic fallback) through an in-process
    ``ScenarioServer`` and reshape the report via ``loadgen.gate_row`` —
    serving throughput rides the same trajectory as the kernel rows."""
    from repro.serve import loadgen
    from repro.serve.server import ScenarioServer

    from .common import note

    reqs = (loadgen.read_trace(SMOKE_TRACE) if os.path.exists(SMOKE_TRACE)
            else loadgen.synthetic_trace(10, 0))
    report = loadgen.replay(ScenarioServer(), reqs, waves=2)
    problems = loadgen.check_report(report)
    if problems:
        raise SystemExit("bench_gate serve replay failed its acceptance "
                         "checks:\n" + "\n".join(problems))
    note(f"serve: {report['n_requests']} requests "
         f"{report['requests_per_s']:.2f} req/s, cache "
         f"{report['cache']['hits']}H/{report['cache']['misses']}M")
    return loadgen.gate_row(report)


def run(out_path: Optional[str] = None) -> dict:
    import jax

    from .common import SMOKE, emit, note, smoke

    # 16 MCS even in smoke: the observable-overhead pairs measure a ~5%
    # timing delta, which 2-MCS µs-scale calls bury in CPU jitter (scan
    # compile time is length-independent, so the longer chunk costs CI
    # nothing); _bench_combo's iters=9 median serves the same purpose
    mcs = smoke(16, 16)
    trials = smoke(2, 4)
    note(f"kernel gate: {LOCAL_KERNELS} x {FAMILIES} on scenario "
         f"{SCENARIOS[0]!r}, + scenarios {SCENARIOS[1:]} per family "
         f"(jnp), + observable-overhead pairs per family, {mcs} MCS "
         f"({len(jax.devices())} device(s))")
    combos = [(family, kernel, SCENARIOS[0], False)
              for family in FAMILIES for kernel in LOCAL_KERNELS]
    combos += [(family, "jnp", scenario, False)
               for scenario in SCENARIOS[1:] for family in FAMILIES]
    # observable-overhead pairs (v4): the on-rows; their off twins are
    # already in the park3 grid above — row_key pairs them by identity
    combos += [(family, "jnp", SCENARIOS[0], True) for family in FAMILIES]
    rows = []
    for family, kernel, scenario, obs in combos:
        row = _bench_combo(family, kernel, scenario, mcs, trials,
                           observables=obs)
        if obs:
            # annotate the on-row with the measured overhead vs its twin
            twin_key = row_key({**row, "observables": False})
            twin = next(r for r in rows if row_key(r) == twin_key)
            overhead = (twin["updates_per_s"] / row["updates_per_s"]
                        - 1.0) if row["updates_per_s"] else float("inf")
            row["derived"] += f" obs_overhead={overhead:+.1%}"
            note(f"observable overhead {family}/{kernel}: {overhead:+.1%} "
                 f"({twin['updates_per_s']:.0f} -> "
                 f"{row['updates_per_s']:.0f} upd/s)")
        rows.append(row)
        emit(row["name"], row["us_per_call"] / 1e6, row["derived"])
    rows.append(_serve_row())
    emit(rows[-1]["name"], rows[-1]["us_per_call"] / 1e6,
         rows[-1]["derived"])
    doc = {
        "schema": SCHEMA,
        "backend": jax.default_backend(),
        "devices": len(jax.devices()),
        "smoke": bool(SMOKE),
        "unix_time": int(time.time()),
        "rows": rows,
    }
    errors = validate_gate_document(doc)
    if errors:                  # the gate gates itself first
        raise SystemExit("bench_gate produced a schema-invalid document:\n"
                         + "\n".join(errors))
    out_path = out_path or os.environ.get("BENCH_GATE_OUT")
    if out_path:
        with open(out_path, "w") as f:
            json.dump(doc, f, indent=1)
        note(f"schema-valid {SCHEMA} document -> {out_path}")
    return doc


def main() -> None:
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", default=None,
                    help="write the BENCH_kernels.json artifact here "
                         "(default: $BENCH_GATE_OUT, or no file)")
    ap.add_argument("--validate", nargs="+", metavar="FILE", default=None,
                    help="validate BENCH_kernels.json documents, "
                         "BENCH_history.jsonl trajectories and/or "
                         "BENCH_JSON row streams instead of benchmarking")
    ap.add_argument("--compare", metavar="BASELINE", default=None,
                    help="diff the sweep against this committed gate "
                         "document; exit non-zero on any matching-row "
                         "updates_per_s regression beyond the threshold")
    ap.add_argument("--candidate", metavar="FILE", default=None,
                    help="with --compare: read the candidate document "
                         "from FILE instead of re-running the sweep")
    ap.add_argument("--regressionThreshold", dest="regression_threshold",
                    type=float, default=0.5,
                    help="fractional updates_per_s drop that fails the "
                         "gate (default 0.5 = fail below half the "
                         "baseline; CI passes 0.75)")
    ap.add_argument("--history", metavar="FILE", default=None,
                    help="append the gate document to this "
                         "BENCH_history.jsonl perf trajectory")
    args = ap.parse_args()
    if args.validate:
        all_errors = []
        for path in args.validate:
            all_errors.extend(validate_file(path))
        if all_errors:
            print("\n".join(all_errors), file=sys.stderr)
            raise SystemExit(1)
        print(f"# {len(args.validate)} file(s) schema-valid",
              file=sys.stderr)
        return
    # read the baseline BEFORE the sweep runs, so `--out X --compare X`
    # means "diff this run against the committed snapshot, then refresh
    # it" — the natural CI invocation — instead of a vacuous self-compare
    baseline = None
    if args.compare:
        with open(args.compare) as f:
            baseline = json.load(f)
    if args.candidate:
        if not args.compare:
            ap.error("--candidate requires --compare")
        with open(args.candidate) as f:
            doc = json.load(f)
        errors = validate_gate_document(doc)
        if errors:
            print("\n".join(f"candidate invalid: {e}" for e in errors),
                  file=sys.stderr)
            raise SystemExit(1)
    else:
        doc = run(out_path=args.out)
    # artifacts land BEFORE the gate can fail: a regressed run must still
    # leave its evidence on disk / in the uploaded trajectory
    if args.history:
        append_history(doc, args.history)
        print(f"# trajectory entry -> {args.history}", file=sys.stderr)
    if args.compare:
        failures = compare_documents(doc, baseline,
                                     args.regression_threshold)
        if failures:
            print("PERF GATE FAILED vs " + args.compare, file=sys.stderr)
            print("\n".join(failures), file=sys.stderr)
            raise SystemExit(1)
        print(f"# perf gate clean vs {args.compare} (threshold "
              f"{args.regression_threshold:.0%})", file=sys.stderr)


if __name__ == "__main__":
    main()
