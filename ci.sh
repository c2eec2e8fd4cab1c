#!/usr/bin/env bash
# CI entry point (reference analog: Jenkinsfile — unit tests, integration
# tests across service seams, and the shell walkthrough).
#
# Usage: bash ci.sh          # full run on the CPU backend
set -euo pipefail
cd "$(dirname "$0")"

# the committed bench trajectory the advisory gates compare against; an
# empty match (no root records left) is an empty array, not a literal glob
shopt -s nullglob
HISTORY=(BENCH_r*.json)
shopt -u nullglob

echo "== pytest (unit + integration + conformance, virtual 8-device mesh)"
python -m pytest tests/ -q -m 'not chaos'

echo "== chaos (fault injection under a fixed seed: failpoints, retry, lease/reissue)"
env SDA_CHAOS_SEED=20260803 python -m pytest tests/ -q -m chaos

echo "== loadgen smoke (fixed seed, closed-loop, zero 5xx, histogram report)"
LOAD_REPORT=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --load --participants 24 --dim 4 \
  --load-arrivals closed --load-concurrency 8 --load-seed 20260803)
LOAD_REPORT="$LOAD_REPORT" python - <<'PY'
import json, os
report = json.loads(os.environ["LOAD_REPORT"].strip().splitlines()[-1])
assert report["ready"] and report["exact"], report
assert report["client_failures"] == 0, report
assert report["errors_5xx"] == 0, report["status_counts"]
assert report["latency_ms"], "empty per-route histogram report"
assert report["phases_ms"], "empty phase histogram report"
# round lifecycle: a healthy load run must never degrade or fail a round
assert report["rounds_degraded"] == 0, report
assert report["rounds_failed"] == 0, report
print(f"loadgen smoke OK: {report['load_requests']} load-phase requests, "
      f"{report['sustained_rps']} rps sustained")
PY

echo "== dead-clerk drill (fixed seed: 1 permanently dead clerk; Shamir degrades bit-exact, additive fails closed)"
DEAD_SHAMIR=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --dead-clerks 1 \
  --chaos-seed 20260803 --chaos-rate 0.05)
DEAD_ADDITIVE=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --dead-clerks 1 \
  --chaos-sharing additive --chaos-seed 20260803 --chaos-rate 0.05)
ROUND_RECORD=$(mktemp /tmp/sda-round-XXXX.json)
DEAD_SHAMIR="$DEAD_SHAMIR" DEAD_ADDITIVE="$DEAD_ADDITIVE" ROUND_RECORD="$ROUND_RECORD" python - <<'PY'
import json, os
shamir = json.loads(os.environ["DEAD_SHAMIR"].strip().splitlines()[-1])
additive = json.loads(os.environ["DEAD_ADDITIVE"].strip().splitlines()[-1])
# packed Shamir: clerking -> degraded -> revealed, bit-exact vs the
# healthy reference (the surviving 7-of-8 quorum reconstructs exactly)
states = [s for s, _ in shamir["round_history"]]
assert shamir["exact"] is True, shamir
assert "degraded" in states and states[-1] == "revealed", states
assert shamir["round_dead_clerks"], shamir
assert shamir["time_to_degraded_s"] and shamir["time_to_degraded_s"] > 0, shamir
# additive: unrecoverable -> terminal 'failed' with a machine-readable
# reason BEFORE the drill deadline (no hang), surfaced as a typed error
assert additive["round_state"] == "failed", additive
assert additive["round_reason"], additive
assert additive["failure"] and additive["failure"]["type"] == "RoundFailed", additive
assert additive["time_to_failed_s"] and additive["time_to_failed_s"] > 0, additive
record = {
    "metric": "time to degraded (dead-clerk drill, 8-clerk packed Shamir over HTTP)",
    "value": shamir["time_to_degraded_s"], "unit": "seconds",
    "platform": "cpu", "seed": shamir["seed"],
    "clerking_deadline_s": 1.5,
}
with open(os.environ["ROUND_RECORD"], "w") as f:
    json.dump(record, f)
print(f"dead-clerk drill OK: shamir {'->'.join(states)} exact={shamir['exact']} "
      f"time_to_degraded={shamir['time_to_degraded_s']}s; "
      f"additive failed in {additive['time_to_failed_s']}s "
      f"({additive['round_reason'][:60]}...)")
PY
# the detection-latency record must parse as a bench record and gate
# (advisory: first record of its metric — it seeds the trailing window)
python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}" "$ROUND_RECORD"
rm -f "$ROUND_RECORD"

echo "== brownout drill (fixed seed: store browns out mid-clerking; breaker trips, sheds 503+Retry-After, recovers; round bit-exact)"
BROWNOUT=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --brownout 1.0 \
  --chaos-seed 20260803 --chaos-rate 0.05)
BROWNOUT_RECORD=$(mktemp /tmp/sda-brownout-XXXX.json)
BROWNOUT="$BROWNOUT" BROWNOUT_RECORD="$BROWNOUT_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["BROWNOUT"].strip().splitlines()[-1])
# the round must survive the brownout window bit-exactly: every admitted
# participation present, reveal exact, despite a second of store failures
assert report["ready"] and report["exact"], report
breaker = report["breaker"]
# the breaker actually did its job: tripped at least once, shed while
# open, half-opened on probes, and CLOSED again after the window healed
assert breaker["times_opened"] >= 1, breaker
assert breaker["state"] == "closed", breaker
counters = report["counters"]
assert counters.get("server.store.breaker.shed", 0) >= 1, counters
assert counters.get("http.status.503", 0) >= 1, counters
# MTTR: first trip -> final recovery, a hair over the 1 s injected
# window (the recovery probe cadence is 0.25 s)
mttr = report["time_to_recover_s"]
assert mttr and 0 < mttr < 10.0, report
record = {
    "metric": "time to recover (store brownout drill, 1s window, breaker threshold 3)",
    "value": mttr, "unit": "seconds",
    "platform": "cpu", "seed": report["seed"],
    "brownout_s": report["brownout_s"],
    "breaker_recovery_s": breaker["recovery_s"],
}
with open(os.environ["BROWNOUT_RECORD"], "w") as f:
    json.dump(record, f)
print(f"brownout drill OK: exact={report['exact']}, breaker opened "
      f"{breaker['times_opened']}x, shed {counters.get('server.store.breaker.shed')} "
      f"op(s), time_to_recover={mttr}s")
PY
# the MTTR record must parse as a bench record and gate (advisory: first
# record of its metric seeds the trailing window)
python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}" "$BROWNOUT_RECORD"
rm -f "$BROWNOUT_RECORD"

echo "== churn drill (fixed seed: ~40% participant churn, crash mid-upload + journal resume + duplicate retries; bit-exact, zero double counts)"
CHURN=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --churn 0.35 \
  --chaos-store sqlite --chaos-seed 20260803 --chaos-rate 0.05)
CHURN_RECORD=$(mktemp /tmp/sda-churn-XXXX.json)
CHURN="$CHURN" CHURN_RECORD="$CHURN_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["CHURN"].strip().splitlines()[-1])
# the exactly-once verdict: nonzero churn actually happened, every
# departure rejoined via its journal, mid-upload crashes replayed
# byte-identically, the equivocation probe was rejected, and the round
# revealed bit-exactly with ZERO double-counted participations
assert report["exact"] is True, report
assert report["participants_churned"] >= 1, report
assert report["participants_resumed"] == report["participants_churned"], report
assert report["participations_replayed"] >= 1, report
assert report["equivocations_undetected"] == 0, report
assert report["equivocations_detected"] >= 1, report
assert report["double_counted"] == 0, report
record = {
    "metric": "churn drill resume wall (12 participants, ~40% churn, journal resume over HTTP)",
    "value": report["time_to_resume_s"], "unit": "seconds",
    "platform": "cpu", "seed": report["seed"],
    "churn_rate": report["churn_rate"],
    "participants_resumed": report["participants_resumed"],
}
with open(os.environ["CHURN_RECORD"], "w") as f:
    json.dump(record, f)
print(f"churn drill OK: {report['participants_churned']} churned, "
      f"{report['participants_resumed']} resumed, "
      f"{report['participations_replayed']} replayed, "
      f"equivocations detected={report['equivocations_detected']} "
      f"undetected={report['equivocations_undetected']}, "
      f"double_counted={report['double_counted']}, exact={report['exact']}")
PY
# the resume-wall record must parse as a bench record and gate (advisory:
# first record of its metric seeds the trailing window)
python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}" "$CHURN_RECORD"
rm -f "$CHURN_RECORD"

echo "== async-plane A/B (same fixed-seed chaos+churn drill, threaded vs asyncio event-loop plane: bit-exact, identical exactly-once counters)"
AB_THREADED=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --churn 0.35 \
  --chaos-store sqlite --chaos-seed 20260803 --chaos-rate 0.05)
AB_ASYNC=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --chaos --churn 0.35 \
  --chaos-store sqlite --chaos-seed 20260803 --chaos-rate 0.05 --async-http)
AB_THREADED="$AB_THREADED" AB_ASYNC="$AB_ASYNC" python - <<'PY'
import json, os
threaded = json.loads(os.environ["AB_THREADED"].strip().splitlines()[-1])
asyncp = json.loads(os.environ["AB_ASYNC"].strip().splitlines()[-1])
assert threaded["http_plane"] == "threaded" and asyncp["http_plane"] == "async"
# the plane must be invisible to the protocol: same fixed seed -> same
# bit-exact reveal, same churn resolution, same exactly-once verdicts
for key in ("exact", "ready", "participants_churned", "participants_resumed",
            "participations_replayed", "equivocations_detected",
            "equivocations_undetected", "double_counted",
            "admitted_participations"):
    assert threaded[key] == asyncp[key], (key, threaded[key], asyncp[key])
assert threaded["exact"] is True, threaded
part = lambda rep: {k: v for k, v in rep["counters"].items()
                    if k.startswith("server.participation.")}
assert part(threaded) == part(asyncp), (part(threaded), part(asyncp))
print(f"async-plane A/B OK: exact on both planes, participation counters "
      f"{part(asyncp)} identical, "
      f"{asyncp['participants_resumed']} resumed on each")
PY

echo "== job-pickup bench (fixed seed: long-poll vs 0.5s polling clerks on the async plane; >=10x lower p99 gated)"
PICKUP_RECORD=$(mktemp /tmp/sda-pickup-XXXX.json)
PICKUP=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --pickup \
  --pickup-snapshots 6 --pickup-interval 0.5 --pickup-wait 10 \
  --pickup-seed 20260803)
PICKUP="$PICKUP" PICKUP_RECORD="$PICKUP_RECORD" python - <<'PY'
import json, os
record = json.loads(os.environ["PICKUP"].strip().splitlines()[-1])
# both modes closed their rounds bit-exactly; the long-poll win is the
# acceptance bar: enqueue->lease p99 at least 10x below the polling
# baseline on the same fixed-seed round
assert record["exact"] is True, record
assert record["samples"] >= 40, record
assert record["value"] is not None and record["value"] > 0, record
assert record["speedup_p99"] and record["speedup_p99"] >= 10.0, record
with open(os.environ["PICKUP_RECORD"], "w") as f:
    json.dump(record, f)
print(f"pickup bench OK: long-poll p99 {record['value']}ms vs polling "
      f"{record['polling']['p99_ms']}ms ({record['speedup_p99']}x, "
      f"{record['samples']} samples)")
PY
# the pickup record (direction=lower) must parse and gate advisory
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$PICKUP_RECORD"
rm -f "$PICKUP_RECORD"

echo "== connection storm (10k held connections on one async-plane sdad worker: zero 5xx, bounded RSS, clean drain)"
STORM_RECORD=$(mktemp /tmp/sda-storm-XXXX.json)
STORM=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --connstorm 10000 \
  --connstorm-waves 2 --connstorm-rss-limit 1024)
STORM="$STORM" STORM_RECORD="$STORM_RECORD" python - <<'PY'
import json, os
record = json.loads(os.environ["STORM"].strip().splitlines()[-1])
# the async-plane capacity verdict: every connection opened and served
# on every wave (10k unless the host fd limit clamps — then the record
# says so), zero 5xx from exhaustion (shedding would be 429/503), RSS
# bounded, and the SIGTERM drain still clean with every socket open
assert record["ok"] is True, record
assert record["errors_5xx"] == 0, record["statuses"]
assert record["transport_failures"] == 0, record
assert record["connect_failures"] == 0, record
assert record["leaked"] == 0, record["drain"]
if not record["clamped_by_fd_limit"]:
    assert record["value"] == 10000, record
assert record["rss_bounded"] is True, record
with open(os.environ["STORM_RECORD"], "w") as f:
    json.dump(record, f)
print(f"connstorm OK: {record['value']} connections held "
      f"({record['per_connection_kb']} KiB/conn growth, RSS "
      f"{record['rss_mb']}MiB <= {record['rss_limit_mb']}MiB), "
      f"{sum(w['requests'] for w in record['waves'])} pings, "
      f"drain leaked={record['leaked']}")
PY
# the connection-capacity record must parse and gate advisory
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$STORM_RECORD"
rm -f "$STORM_RECORD"

echo "== devscale drill (fixed seed: sharded tile schedule, interpret-mode Pallas external randomness, shrunk dim; bit-exact vs oracle, zero retraces, HBM under watermark)"
DEVSCALE_RECORD=$(mktemp /tmp/sda-devscale-XXXX.json)
DEVSCALE=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --devscale \
  --devscale-dim 25000 --devscale-participants 8 --devscale-shards 4x2 \
  --devscale-pallas --devscale-rounds 3 --devscale-seed 20260804)
DEVSCALE="$DEVSCALE" DEVSCALE_RECORD="$DEVSCALE_RECORD" python - <<'PY'
import json, os
record = json.loads(os.environ["DEVSCALE"].strip().splitlines()[-1])
# the model-scale schedule at a CI-sized dim: the sharded+streamed round
# under interpret-mode Pallas (external randomness) must reveal the
# oracle lane's bytes exactly, reuse ONE compiled shape per stage with
# zero retraces, keep its HBM promise, and the clerk-pipeline-fed
# device-tile sink must reproduce the device-generated lane bit-for-bit
assert record["ok"] is True, record
assert record["exact"] is True, record["oracle"]
assert record["pallas"] is True and record["pallas_interpret"] is True, record
assert record["platform"] == "cpu" and record["device_count"] == 8, record
assert record["retraces"] == 0 and record["warm_program_reused"], record
assert all(v == 1 for v in record["compiled_shapes"].values()), record["compiled_shapes"]
assert record["clerk_fed"]["exact"] is True, record["clerk_fed"]
assert record["clerk_fed"]["sink_misses"] == 0, record["clerk_fed"]
assert record["scan_lane"]["exact"] is True, record["scan_lane"]
assert record["hbm"]["within_watermark"] is True, record["hbm"]
assert record["tile_rule"] == "hbm_watermark", record
with open(os.environ["DEVSCALE_RECORD"], "w") as f:
    json.dump(record, f)
print(f"devscale drill OK: dim {record['dim']} over {record['p_shards']}x"
      f"{record['d_shards']} mesh, tile {record['dim_tile']} "
      f"(hbm ratio {record['hbm_watermark_ratio']}), "
      f"{record['value']} el/s, retraces {record['retraces']}, "
      f"sink hits {record['clerk_fed']['sink_hits']}")
PY
# the devscale record must parse and gate advisory (its comparability
# tags — dim/p_shards/d_shards/pallas — seed a fresh lineage vs the
# committed dim-1e8 record)
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$DEVSCALE_RECORD"
rm -f "$DEVSCALE_RECORD"

echo "== tree drill (fixed seed: 2-level tree over sqlite+HTTP, ~10% leaf dropout, bit-exact vs flat reference; simulated 1e5-participant record)"
TREE=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --tree --participants 24 --dim 4 \
  --tree-group-size 6 --tree-seed 20260803 --tree-dropout 0.1 --tree-sim 100000)
TREE_RECORD=$(mktemp /tmp/sda-tree-XXXX.json)
TREE="$TREE" TREE_RECORD="$TREE_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["TREE"].strip().splitlines()[-1])
# the real-crypto rung: a 2-level tree (G leaf rounds + 1 root round)
# over sqlite through real HTTP, leaf dropout injected, every level's
# round revealed, and the ROOT output bit-exact against BOTH the
# surviving-devices expectation and a real flat reference round
assert report["depth"] == 2, report["depth"]
assert report["groups"] >= 2, report
assert report["exact"] is True, report
assert report["flat_exact"] is True, report
assert report["root_state"] == "revealed", report
assert report["participants_dropped"] >= 1, report
# relay accounting: one re-share per leaf group, masks forwarded in-band
assert report["relays"] == report["groups"], report
assert report["counters"].get("relay.masks_forwarded", 0) >= 1, report["counters"]
# tree linkage visible on the round documents (any worker can diagnose)
assert report["root_children"] and len(report["root_children"]) == report["groups"], report
# the simulated population rung: fixed-seed 1e5-participant 2-level tree,
# bit-exact vs the flat walk, peak per-node memory BOUNDED by the batch
sim = report["sim"]
assert sim["participants"] == 100000, sim
assert sim["depth"] == 2, sim
assert sim["exact"] is True, sim
assert sim["bounded"] is True, sim
assert sim["peak_node_elements"] <= sim["bound_elements"], sim
# the MEASURED verdict: tracemalloc peak of the streaming pass stays
# under the batch-derived bound, independent of the population
assert sim["peak_pass_bytes"] <= sim["bound_pass_bytes"], sim
with open(os.environ["TREE_RECORD"], "w") as f:
    json.dump(sim, f)
print(f"tree drill OK: {report['groups']} groups, "
      f"{report['participants_dropped']} dropped, exact={report['exact']} "
      f"flat_exact={report['flat_exact']}; sim 1e5 exact={sim['exact']} "
      f"bounded={sim['bounded']} ({sim['value']} participants/sec)")
PY
# the simulated participants=1e5 record must parse as a bench record and
# gate advisory via sda-bench --check (first record of its metric seeds
# the trailing window; CPU rung numbers are advisory by policy)
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$TREE_RECORD"
rm -f "$TREE_RECORD"

echo "== wire codec A/B (fixed seed: same round JSON vs binary, bit-exact both ways)"
CODEC_JSON=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --load --participants 16 --dim 64 \
  --load-arrivals closed --load-concurrency 4 --load-seed 20260803 \
  --load-store memory --load-codec json)
CODEC_BIN=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --load --participants 16 --dim 64 \
  --load-arrivals closed --load-concurrency 4 --load-seed 20260803 \
  --load-store memory --load-codec bin)
CODEC_JSON="$CODEC_JSON" CODEC_BIN="$CODEC_BIN" python - <<'PY'
import json, os
reports = {}
for codec in ("json", "bin"):
    report = json.loads(os.environ[f"CODEC_{codec.upper()}"].strip().splitlines()[-1])
    # the wire codec must never change the round's outcome
    assert report["ready"] and report["exact"], (codec, report)
    assert report["client_failures"] == 0, (codec, report)
    assert report["codec"] == codec, (codec, report["codec"])
    reports[codec] = report
counters = {c: reports[c].get("codec_counters") or {} for c in reports}
# the bin swarm actually spoke binary; the json swarm never did
assert counters["bin"].get("http.codec.bin.in", 0) > 0, counters["bin"]
assert counters["json"].get("http.codec.bin.in", 0) == 0, counters["json"]
for codec, report in reports.items():
    print(f"codec {codec}: exact={report['exact']} "
          f"rps={report['sustained_rps']} counters={counters[codec]}")
PY

echo "== fleet drill (fixed seed: 2 sdad processes, one shared sqlite store, chaos on, bit-exact)"
FLEET_RECORD=$(mktemp /tmp/sda-fleet-XXXX.json)
FLEET_REPORT=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --load --participants 24 --dim 4 \
  --load-arrivals closed --load-concurrency 8 --load-seed 20260803 \
  --load-store sqlite --load-fleet 2 --load-chaos-rate 0.05)
FLEET_REPORT="$FLEET_REPORT" FLEET_RECORD="$FLEET_RECORD" python - <<'PY'
import json, os
record = json.loads(os.environ["FLEET_REPORT"].strip().splitlines()[-1])
# both rungs (1 worker, 2 workers) must close the round bit-exactly
# with zero lost admitted participations and zero leaked requests —
# even with ~5% of requests 500ing inside the worker processes
assert record["fleet_nodes"] == 2, record
assert record["ready"] and record["exact"], record
assert record["client_failures"] == 0, record
assert record["leaked"] == 0, record
assert record["chaos_rate"] > 0, record
# every worker actually served load-phase traffic
assert all(rps > 0 for rps in record["per_node_load_rps"].values()), \
    record["per_node_load_rps"]
assert isinstance(record["scaling_efficiency"], float), record
with open(os.environ["FLEET_RECORD"], "w") as f:
    json.dump(record, f)
print(f"fleet drill OK: {record['value']} rps @2 workers vs "
      f"{record['baseline_rps']} @1, efficiency "
      f"{record['scaling_efficiency']} ({record['host_cores']} cores), "
      f"exact={record['exact']}")
PY
# the fresh scaling record must parse as a bench record and gate
# (advisory: scaling efficiency is bounded by the CI host's core count)
python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}" "$FLEET_RECORD"
rm -f "$FLEET_RECORD"

echo "== forensics drill (fixed seed: churn+chaos fleet round with the flight recorder on; every process exits, then sda-trace explain reconstructs the round from the spools alone)"
SPOOL_DIR=$(mktemp -d /tmp/sda-spool-XXXX)
FORENSICS_REPORT=$(env JAX_PLATFORMS=cpu SDA_FLIGHT_RECORDER="$SPOOL_DIR" \
  python -m sda_tpu.cli.sim --load --participants 24 --dim 4 \
  --load-arrivals closed --load-concurrency 8 --load-seed 20260803 \
  --load-store sqlite --load-fleet 2 --load-chaos-rate 0.05 --load-churn 0.3)
# the sim process and both fleet workers have exited: the JSONL spool
# segments under $SPOOL_DIR are ALL that remains of the round's telemetry
FORENSICS_REPORT="$FORENSICS_REPORT" SPOOL_DIR="$SPOOL_DIR" python - <<'PY'
import json, os
report = json.loads(os.environ["FORENSICS_REPORT"].strip().splitlines()[-1])
# the recorder-on run itself must stay bit-exact (no protocol bytes change)
assert report["ready"] and report["exact"], report
assert report["output_sha256"], report
from sda_tpu.obs import forensics
spool = forensics.load_spool(os.environ["SPOOL_DIR"])
rep = forensics.explain(spool, report["aggregation"])
# all three processes (sim swarm + 2 sdad workers) spooled segments
assert len(rep["processes"]) >= 3, rep["processes"]
# the round story is complete: every admitted participation visible,
# the ledger reaches revealed, chaos faults attributed site+kind
assert rep["participations"]["created"] == report["admitted_participations"], \
    (rep["participations"], report["admitted_participations"])
assert rep["final_state"] == "revealed", rep["states"]
assert rep["faults"], "no chaos faults attributed in the spools"
assert all(f["site"] and f["kind"] for f in rep["faults"]), rep["faults"]
# bit-exact reveal recorded: the spooled reveal span's digest matches the
# loadgen oracle's digest of the expected plaintext sum
assert rep["reveal"] and rep["reveal"]["output_sha256"] == report["output_sha256"], \
    (rep["reveal"], report["output_sha256"])
print(f"forensics drill OK: {rep['spans']} spans from "
      f"{len(rep['processes'])} dead processes, "
      f"{rep['participations']['created']} participations, "
      f"{len(rep['faults'])} faults attributed, states "
      f"{'->'.join(s['state'] for s in rep['states'])}, reveal digest match")
PY
# the CLI spelling must agree with the library pass (and exit 0)
env SDA_FLIGHT_RECORDER="$SPOOL_DIR" python -m sda_tpu.cli.tracecli segments > /dev/null
env SDA_FLIGHT_RECORDER="$SPOOL_DIR" python -m sda_tpu.cli.tracecli slo > /dev/null
rm -rf "$SPOOL_DIR"

echo "== recorder overhead bench (span hot path, recorder off vs on; BENCH record gated advisory)"
REC_RECORD=$(mktemp /tmp/sda-recbench-XXXX.json)
python -m sda_tpu.loadgen.recorderbench --spans 20000 --max-overhead-pct 400 > "$REC_RECORD"
python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}" "$REC_RECORD"
rm -f "$REC_RECORD"

echo "== soak drill (fixed seed: 2 tenants x 3 pipelined epochs, sqlite + HTTP fleet of 2, ~10% chaos, churn armed; bit-exact per epoch, flat store after retention)"
SOAK_RECORD=$(mktemp /tmp/sda-soak-XXXX.json)
SOAK=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --soak \
  --soak-tenants 2 --soak-epochs 3 --soak-participants 4 \
  --soak-store sqlite --soak-fleet 2 --soak-chaos-rate 0.1 \
  --soak-churn 0.4 --soak-seed 20260803)
SOAK="$SOAK" SOAK_RECORD="$SOAK_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["SOAK"].strip().splitlines()[-1])
# the continuous-service verdict: every tenant's every epoch revealed
# bit-exactly, epoch R+1 collected while epoch R clerked (server-stamped
# history), and nothing leaked across epochs or tenants
assert report["exact"] is True, report
assert report["rounds_exact"] == report["rounds"] == 6, report
assert report["pipelined"] is True, report["pipelined_pairs"]
assert report["leaks"] == 0, report
assert report["client_failures"] == 0, report
# the scheduler really was contended (two handles race every mint) and
# every epoch was minted exactly once
sched = report["scheduler"]
assert sched["epochs_minted"] == 6, sched
# churned devices all rejoined via their journals
churn = report["churn"]
assert churn["participants_churned"] >= 1, churn
assert churn["participants_resumed"] == churn["participants_churned"], churn
# retention kept the store flat: every revealed round purged, zero
# leaked rows between epoch 2 and the final epoch, worker RSS flat
retention = report["retention"]
assert retention["purged_rounds"] == 6, retention
assert retention["store_rows_flat"] is True, retention
assert retention["rss_flat"] in (True, None), retention
assert report["fleet"]["leaked"] == 0, report["fleet"]
with open(os.environ["SOAK_RECORD"], "w") as f:
    json.dump(report, f)
print(f"soak drill OK: {report['rounds_exact']}/{report['rounds']} epochs "
      f"exact, pipelined {report['pipelined_pairs']}, "
      f"{retention['purged_rounds']} rounds purged, store rows "
      f"{retention['store_rows_epoch2']}->{retention['store_rows_final']}, "
      f"{report['value']} rounds/hour sustained")
PY
# the rounds_per_hour record must parse as a bench record and gate
# (advisory: first record of its metric seeds the trailing window)
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$SOAK_RECORD"
rm -f "$SOAK_RECORD"

echo "== analytics drill (fixed seed: histogram + count-min tenants, 2 recurring epochs each, sqlite+HTTP; bit-exact sums, decoder errors within declared contracts)"
ANA_RECORD=$(mktemp /tmp/sda-analytics-XXXX.json)
ANA=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --analytics histogram,countmin \
  --analytics-participants 4 --analytics-epochs 2 \
  --analytics-store sqlite --analytics-http --analytics-seed 20260806)
ANA="$ANA" ANA_RECORD="$ANA_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["ANA"].strip().splitlines()[-1])
# the analytics verdict: every tenant-epoch's revealed sum equals the
# plaintext sum bit-exactly, and every decoded answer stays within the
# encoder's declared error contract against the seeded ground truth
assert report["exact"] is True, report
assert report["rounds_exact"] == report["rounds"] == 4, report
assert report["bounds_ok"] is True, report
assert report["rounds_within_bounds"] == 4, report
assert report["leaks"] == 0, report
assert report["client_failures"] == 0, report
# the multi-tenant scheduler drove every round: both schedules
# installed, every epoch minted/closed through the cadence-gated tick
sched = report["scheduler"]
assert sched["installed"] == 2, sched
assert sched["epochs_closed"] == 4, sched
per = report["per_tenant"]
hist = per["analytics-histogram-0"]
cm = per["analytics-countmin-1"]
# the exact encoder really was exact; the sketch stayed under eps*N
# with zero delta-budget breaches and no count-min underestimates
assert all(c["error"] == 0.0 for c in hist["checks"]), hist["checks"]
assert all(c["error"] <= c["bound"] and c["underestimates"] == 0
           and c["eps_violations"] <= c["delta_allowance"]
           for c in cm["checks"]), cm["checks"]
with open(os.environ["ANA_RECORD"], "w") as f:
    json.dump(report, f)
print(f"analytics drill OK: {report['rounds_exact']}/{report['rounds']} "
      f"rounds exact, {report['rounds_within_bounds']} within contract, "
      f"{report['value']} values/s")
PY
# the values/s record must parse as a bench record and gate (advisory:
# first record of its metric seeds the trailing window)
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$ANA_RECORD"
rm -f "$ANA_RECORD"

echo "== FL drill (fixed seed: LeNet secure FedAvg, 8 devices, ~25% churn, 1 dead clerk, sqlite+HTTP; target accuracy reached, bit-exact aggregate every round)"
FL_RECORD=$(mktemp /tmp/sda-fl-XXXX.json)
FL=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --fl --participants 8 \
  --fl-family lenet --fl-rounds 3 --fl-local-steps 6 --fl-batch 32 \
  --fl-target 0.8 --fl-churn 0.25 --fl-dead-clerks 1 \
  --fl-store sqlite --fl-http --fl-seed 20260803)
FL="$FL" FL_RECORD="$FL_RECORD" python - <<'PY'
import json, os
report = json.loads(os.environ["FL"].strip().splitlines()[-1])
# the canonical-workload verdict: R secure FedAvg rounds over the real
# stack reach the target accuracy, and EVERY revealed round is bit-exact
# vs the plaintext quantized sum of its frozen participant set — under
# nonzero device dropout AND a permanently dead committee clerk
assert report["exact"] is True, report["failure_samples"]
assert report["rounds_exact"] == report["rounds_run"] == 3, report
assert report["reached_target"] is True, report["accuracy_by_round"]
assert report["rounds_to_target"] <= 3, report
assert report["final_accuracy"] >= report["target_accuracy"], report
# the real (shrunk) LeNet trained and shipped: 61k-dim encoded deltas
assert report["family"] == "lenet" and report["dim"] > 60000, report
# availability churn actually happened and resolved exactly-once: every
# departure resumed via its journal, mid-upload crashes replayed
# byte-identically, pre-upload crashes ARE the rounds' dropout
churn = report["churn"]
assert churn["participants_churned"] >= 1, churn
assert churn["participants_resumed"] == churn["participants_churned"], churn
assert churn["participations_replayed"] >= 1, churn
assert churn["dropped_from_rounds"] >= 1, churn
assert any(r["dropped"] >= 1 for r in report["per_round"]), report["per_round"]
# the dead clerk degraded every round through the lifecycle plane — and
# the surviving Shamir quorum still revealed (never hung, never failed)
assert report["degraded_rounds"] == 3, report
assert all(r["state"] == "revealed" for r in report["per_round"]), report
assert report["leaks"] == 0 and report["client_failures"] == 0, report
with open(os.environ["FL_RECORD"], "w") as f:
    json.dump(report, f)
acc = "->".join(str(a) for a in report["accuracy_by_round"])
print(f"FL drill OK: accuracy {acc} (target {report['target_accuracy']} in "
      f"{report['rounds_to_target']} round(s)), {report['rounds_exact']}/3 "
      f"bit-exact, {churn['participants_churned']} churned/"
      f"{churn['participants_resumed']} resumed/"
      f"{churn['participations_replayed']} replayed, "
      f"{report['degraded_rounds']} degraded round(s)")
PY
# the accuracy-vs-rounds record (direction=lower: MORE rounds to target
# is the regression) must parse as a bench record and gate advisory via
# sda-bench --check (first record of its metric seeds the window)
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$FL_RECORD"
rm -f "$FL_RECORD"
# the participate-input micro-bench behind the ndarray pass-through fix:
# one vectorized normalization at model dim instead of 1e5 int() calls
python -m sda_tpu.loadgen.inputbench --dim 100000

echo "== poisoning drill (fixed seed: boost:-8 at r=0.4 — undefended degrades, norm-clip defense recovers, BOTH bit-exact with clerk-side detections; tree-mode trimmed mean)"
# A/B/C at one seed: the same seeded attacker plan (chaos/poison.py)
# corrupts the same devices in all poisoned legs, so the accuracy
# deltas are attributable to the defense, not the draw
POISON_ARGS=(--fl --participants 5 --fl-rounds 2 --fl-seed 3)
CLEAN=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim "${POISON_ARGS[@]}")
UNDEF=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim "${POISON_ARGS[@]}" \
  --poison 0.4)
DEFEND=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim "${POISON_ARGS[@]}" \
  --poison 0.4 --fl-norm-clip 0.5)
# tree-mode leg: signflip attackers inside leaf groups, robust
# (trimmed-mean) recipient aggregation over unmasked leaf subtotals
TREE=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --fl --participants 9 \
  --fl-rounds 2 --fl-seed 5 --fl-tree-group 3 \
  --poison 0.25 --poison-kind signflip --fl-tree-robust)
POISON_RECORD=$(mktemp /tmp/sda-poison-XXXX.json)
CLEAN="$CLEAN" UNDEF="$UNDEF" DEFEND="$DEFEND" TREE="$TREE" \
  POISON_RECORD="$POISON_RECORD" python - <<'PY'
import json, os
last = lambda k: json.loads(os.environ[k].strip().splitlines()[-1])
clean, undef, defend, tree = map(last, ("CLEAN", "UNDEF", "DEFEND", "TREE"))
# bit-exactness is unconditional: poisoning corrupts INPUTS, never the
# protocol — every revealed round still equals the plaintext quantized
# sum of what was actually submitted (taint adds the field modulus p,
# invisible mod p, so detection and exactness coexist)
for leg in (clean, undef, defend, tree):
    assert leg["exact"] is True, leg.get("failure_samples")
    assert leg["rounds_exact"] == leg["rounds_run"], leg
    assert leg["client_failures"] == 0, leg
# undefended: the boosted updates wreck the model. defended: the codec's
# by-construction L2 projection caps attacker mass; accuracy recovers
assert clean["attack"] is None, clean["attack"]
assert clean["final_accuracy"] >= 0.9, clean["accuracy_by_round"]
assert undef["final_accuracy"] <= clean["final_accuracy"] - 0.5, (
    undef["accuracy_by_round"])
assert defend["final_accuracy"] >= 0.9, defend["accuracy_by_round"]
# both poisoned legs selected the SAME seeded attackers and every
# attacker's tainted (out-of-field) share upload was counted by clerks
for leg in (undef, defend):
    atk = leg["attack"]
    assert atk["attackers_total"] >= 1, atk
    assert atk["shares_tainted"] == atk["attackers_total"], atk
    assert atk["out_of_range_detections"] >= atk["attackers_total"], atk
assert undef["attack"]["attackers_by_round"] == \
    defend["attack"]["attackers_by_round"], (undef["attack"],
                                             defend["attack"])
assert undef["attack"]["defended"] is False, undef["attack"]
assert defend["attack"]["defended"] is True, defend["attack"]
# the quantizer block surfaces the defense and its headroom
assert defend["quantizer"]["norm_clip"] == 0.5, defend["quantizer"]
assert defend["quantizer"]["headroom_margin"] > 0, defend["quantizer"]
# tree mode: trimmed mean over per-leaf subtotals holds the target
# under in-leaf signflip attackers, with detections at leaf clerks
assert tree["reached_target"] is True, tree["accuracy_by_round"]
t = tree["attack"]
assert t["tree_robust"] is True and t["attackers_total"] >= 1, t
assert t["out_of_range_detections"] >= 1, t
assert all(r["robust_leaves"] == 3 for r in tree["per_round"]), (
    tree["per_round"])
record = {
    "metric": ("defended final accuracy under boost:-8 poisoning "
               "(r=0.4, L2 norm clip 0.5, secure FedAvg, 5 devices)"),
    "value": defend["final_accuracy"],
    "direction": "higher",
    "unit": "accuracy",
    "platform": defend["platform"],
    "seed": defend["seed"],
    "attack": {
        "kind": defend["attack"]["kind"],
        "rate": defend["attack"]["rate"],
        "clean_final": clean["final_accuracy"],
        "undefended_final": undef["final_accuracy"],
        "defended_final": defend["final_accuracy"],
        "recovery": round(defend["final_accuracy"]
                          - undef["final_accuracy"], 4),
        "detections": defend["attack"]["out_of_range_detections"],
        "tree_robust_final": tree["final_accuracy"],
    },
}
with open(os.environ["POISON_RECORD"], "w") as f:
    json.dump(record, f)
print(f"poisoning drill OK: clean {clean['final_accuracy']} / undefended "
      f"{undef['final_accuracy']} / defended {defend['final_accuracy']} "
      f"(recovery +{record['attack']['recovery']}), "
      f"{defend['attack']['out_of_range_detections']} clerk detections, "
      f"tree trimmed-mean {tree['final_accuracy']}; all legs bit-exact")
PY
# the defended-accuracy record (direction=higher: a defense that stops
# recovering IS the regression) gates advisory via sda-bench --check
python -m sda_tpu.cli.bench --check --advisory "${HISTORY[@]}" "$POISON_RECORD"
rm -f "$POISON_RECORD"

echo "== trace smoke (fixed seed: Chrome-trace export, one connected round trace, bit-exact)"
TRACE_OUT=$(mktemp /tmp/sda-trace-XXXX.json)
TRACE_REPORT=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim --load --participants 12 --dim 4 \
  --load-arrivals closed --load-concurrency 4 --load-seed 20260803 \
  --load-store memory --trace-out "$TRACE_OUT")
TRACE_REPORT="$TRACE_REPORT" TRACE_OUT="$TRACE_OUT" python - <<'PY'
import json, os
report = json.loads(os.environ["TRACE_REPORT"].strip().splitlines()[-1])
# the round result must stay bit-exact with tracing enabled
assert report["ready"] and report["exact"], report
trace = json.load(open(os.environ["TRACE_OUT"]))  # must parse as JSON
spans = [e for e in trace["traceEvents"] if e.get("ph") == "X"]
by_id = {e["args"]["span_id"]: e for e in spans}
traces = {}
for e in spans:
    traces.setdefault(e["args"]["trace_id"], []).append(e)
round_traces = 0
for members in traces.values():
    roles = {e["name"].split(" ")[0].split(".")[0] for e in members}
    # cross-process-connected: a server span whose parent is a client
    # attempt span proves the trace crossed the HTTP hop
    crossed = any(
        e["name"].startswith("http.server")
        and by_id.get(e["args"].get("parent_id", ""), {}).get("name") == "http.attempt"
        for e in members)
    if {"participant", "server", "clerk", "recipient"} <= roles and crossed:
        round_traces += 1
assert round_traces >= 1, f"no connected round trace among {len(traces)}"
print(f"trace smoke OK: {len(spans)} spans, {round_traces} connected round trace(s)")
PY
rm -f "$TRACE_OUT"

echo "== device perf plane (fixed seed: cost block + compile counters + advisory regression gate)"
PERF_REPORT=$(env JAX_PLATFORMS=cpu python -m sda_tpu.cli.sim \
  --participants 16 --dim 96 --clerks 8 --verify)
PERF_REPORT="$PERF_REPORT" python - <<'PY'
import json, os
report = json.loads(os.environ["PERF_REPORT"].strip().splitlines()[-1])
assert report["exact"], report
assert report["platform"] == "cpu" and report["device_kind"], report
cost = report["cost"]  # counts XLA takes from the shapes: any backend
assert cost["flops"] > 0 and cost["bytes"] > 0, cost
assert cost["arithmetic_intensity"] > 0, cost
assert cost["hbm_peak_bytes"] > 0, cost
# a roofline is a statement about a chip: the CPU is not in the peak
# table (devprof.CHIP_PEAKS), so a CPU run must not carry one
assert "roofline" not in report, report["roofline"]
compile_counters = {k: v for k, v in report["counters"].items()
                    if k.startswith("xla.compile.")}
assert compile_counters, report["counters"]
assert report["xla"]["functions"]["mesh.simpod.round"]["retraces"] == 0
print(f"device perf plane OK: AI={cost['arithmetic_intensity']}, "
      f"no roofline on {report['device_kind']}, "
      f"compile counters {compile_counters}")
PY
# advisory on CPU: CPU numbers are not gated, but a malformed committed
# record still fails CI (exit 2); nothing to check once none is committed
if [ "${#HISTORY[@]}" -gt 0 ]; then
  python -m sda_tpu.obs.regress --advisory "${HISTORY[@]}"
fi

echo "== CLI walkthrough (real sdad + sda over HTTP)"
env JAX_PLATFORMS=cpu bash docs/walkthrough.sh | tail -1 | {
  read -r reveal
  echo "reveal: $reveal"
  [ "$reveal" = "0 2 2 4 4 6 6 8 8 10" ] || { echo "walkthrough output mismatch"; exit 1; }
}

echo "== examples (protocol-over-REST + streamed checkpoint/resume + embedded)"
env JAX_PLATFORMS=cpu python examples/federated_http.py
env JAX_PLATFORMS=cpu python examples/streamed_checkpoint.py
env JAX_PLATFORMS=cpu python examples/embedded_participant.py

echo "CI OK"
