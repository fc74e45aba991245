#!/usr/bin/env bash
# Smoke test of the serving ops plane: boot a query server against a tiny
# trained engine, then hit every operational endpoint from the OUTSIDE
# (curl over real HTTP, the way a probe/load balancer/scrape job would)
# and assert 200 + well-formed JSON / Prometheus text.
#
# Endpoints covered: /healthz /readyz /metrics /logs.json /slo.json
# /qos.json (plus one real /queries.json POST so logs, histograms and
# the SLO engine have live data to report, and a rapid-fire burst so
# admission control demonstrably sheds with 429 + Retry-After).
#
# Runs hermetically: memory storage, ephemeral port, CPU-pinned JAX.
# Exit 0 = all checks passed. Wired into tier-1 via
# tests/test_smoke_endpoints.py.
set -euo pipefail

cd "$(dirname "$0")/.."

WORKDIR="$(mktemp -d -t pio-tpu-smoke-XXXXXX)"
SERVER_PID=""
CHAOS_PID=""
cleanup() {
    [ -n "$SERVER_PID" ] && kill "$SERVER_PID" 2>/dev/null || true
    [ -n "$CHAOS_PID" ] && kill "$CHAOS_PID" 2>/dev/null || true
    [ -n "$SERVER_PID" ] && wait "$SERVER_PID" 2>/dev/null || true
    [ -n "$CHAOS_PID" ] && wait "$CHAOS_PID" 2>/dev/null || true
    rm -rf "$WORKDIR"
}
trap cleanup EXIT

export JAX_PLATFORMS=cpu
export PIO_TPU_HOME="$WORKDIR/home"
mkdir -p "$PIO_TPU_HOME"
PORT_FILE="$WORKDIR/port"

fail() { echo "FAIL: $*" >&2; exit 1; }

# ------------------------------------------------------------------- lint
# The project-native static analyzer must pass clean over the tree —
# cheapest check first, no server boot needed.
python -m pio_tpu.tools.cli lint pio_tpu tests \
    || fail "pio lint found violations"
echo "ok   pio lint clean"

# The hot-path contract is CI-enforced here: the three interprocedural
# rules must report zero findings on their own (not just be drowned in
# a clean aggregate), the seeded roots must all be discovered, and the
# effect fixpoint must stay within its latency budget on this host.
python -m pio_tpu.tools.cli lint pio_tpu tests --json \
    --rules hotpath-blocking,hotpath-zero-copy,shm-frame-layout \
    | python -c '
import json, sys
doc = json.load(sys.stdin)
assert doc["count"] == 0, f"hot-path/layout findings: {doc}"
' || fail "hot-path contract rules not clean"
echo "ok   hotpath-blocking / hotpath-zero-copy / shm-frame-layout clean"

python -m pio_tpu.tools.cli lint --dump-effects pio_tpu | python -c '
import json, sys
doc = json.load(sys.stdin)
roots = {r["function"].rsplit(".", 1)[-1] + ":" + r["marker"]
         for r in doc["roots"]}
need = {
    "query:hotpath",              # query-server request handler
    "_run:hotpath",               # _MicroBatcher dispatch / LaneDrainer
    "submit:hotpath",             # _MicroBatcher admission
    "dispatch_bucketed:hotpath",  # bucket executor
    "submit:zerocopy",            # lane submit path
    "pack_query_i8:zerocopy",     # int8 packed frame
    "unpack_query_i8:zerocopy",
    # ISSUE 13: evloop front + packed zero-copy wire
    "_serve_one:hotpath",         # evloop per-request pipeline
    "submit_packed:zerocopy",     # lane submit of a wire frame
    "_submit_payload:zerocopy",   # shared slot/doorbell path
    "packed_frame_ok:zerocopy",   # structural frame check
    "_query_packed:zerocopy",     # packed HTTP handler
    "_packed_view:zerocopy",      # socket-buffer slice helper
}
missing = need - roots
assert not missing, f"hot-path roots missing from --dump-effects: {missing}"
fams = doc["frames"]
for fam in ("lane-slot", "metrics-stripe", "pel2-record"):
    assert fams.get(fam, {}).get("verified"), f"frame family {fam}: {fams.get(fam)}"
' || fail "--dump-effects roots/frames incomplete"
echo "ok   dump-effects lists every seeded hot-path root + frame family"

python - <<'PY' || fail "effects+contracts exceeded the 10s lint budget"
import time
from pio_tpu.analysis.contracts import get_contracts
from pio_tpu.analysis.core import (
    Finding, LintContext, collect_files, parse_module,
)
from pio_tpu.analysis.effects import EffectAnalysis

mods = [m for m in (parse_module(p) for p in collect_files(["pio_tpu"]))
        if not isinstance(m, Finding)]
t0 = time.monotonic()
EffectAnalysis(mods)
get_contracts(mods, LintContext())
dt = time.monotonic() - t0
assert dt < 10.0, f"effects+contracts took {dt:.1f}s (budget 10s)"
print(f"     effects + contracts over {len(mods)} modules: {dt:.2f}s")
PY
echo "ok   effect fixpoint + contract extraction within budget"

# ------------------------------------------------ contract surfaces
# ISSUE 20: the contract-drift rules must be registered, clean on
# their own (not just drowned in a clean aggregate), and the dump
# inventory must cover the cross-process surface end to end.
python -m pio_tpu.tools.cli lint --list-rules | python -c '
import sys
have = {line.split()[0] for line in sys.stdin if line.strip()}
need = {"endpoint-drift", "header-drift", "knob-default-drift",
        "knob-doc-drift", "failpoint-coverage"}
missing = need - have
assert not missing, f"contract rules not registered: {missing}"
' || fail "contract rules missing from --list-rules"
echo "ok   all five contract-drift rules registered"

python -m pio_tpu.tools.cli lint pio_tpu tests --json \
    --rules endpoint-drift,header-drift,knob-default-drift,knob-doc-drift,failpoint-coverage \
    | python -c '
import json, sys
doc = json.load(sys.stdin)
assert doc["count"] == 0, f"contract-drift findings: {doc}"
' || fail "contract-drift rules not clean"
echo "ok   contract-drift rules clean over the tree"

python -m pio_tpu.tools.cli lint --dump-contracts pio_tpu tests \
    | python -c '
import json, sys
doc = json.load(sys.stdin)
eps = set(doc["endpoints"])
need = {"/fleet.json", "/train.json", "/device.json", "/stats.json",
        "/slo.json", "/qos.json", "/storage.json", "/rollout.json",
        "/queries.json", "/events.json", "/router.json"}
missing = need - eps
assert not missing, f"endpoints missing from --dump-contracts: {missing}"
fleet = doc["endpoints"]["/fleet.json"]
assert fleet["producers"] and fleet["keys"] and fleet["consumers"], \
    "/fleet.json inventory must carry producers, keys and consumers"
hdrs = set(doc["headers"])
for h in ("x-pio-priority", "x-pio-deadline-ms", "x-pio-trace"):
    assert h in hdrs, f"header {h} missing from --dump-contracts"
from pio_tpu.utils.knobs import KNOBS
knobs = doc["knobs"]
unlisted = set(KNOBS) - set(knobs)
assert not unlisted, f"registry knobs missing from dump: {unlisted}"
for name in KNOBS:
    assert "default" in knobs[name], f"{name} has no canonical default"
' || fail "--dump-contracts inventory incomplete"
echo "ok   dump-contracts inventories endpoints, headers + every knob"

# Boot: train the recommendation template on a tiny in-memory corpus,
# serve it with a declared SLO, publish the ephemeral port, then park.
python - "$PORT_FILE" <<'PY' &
import datetime as dt
import os
import signal
import sys

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(8):
    for i in range(6):
        in_block = (u < 4) == (i < 3)
        le.insert(
            Event("rate", "user", f"u{u}", "item", f"i{i}",
                  properties={"rating": 5.0 if in_block else 1.0},
                  event_time=t0),
            app_id,
        )
variant = variant_from_dict({
    "id": "smoke-rec",
    "engineFactory": "templates.recommendation",
    "datasource": {"params": {"app_name": "smoke"}},
    "algorithms": [{"name": "als", "params": {
        "rank": 4, "num_iterations": 4, "lambda_": 0.1}}],
})
engine, ep = build_engine(variant)
run_train(engine, ep, variant, ctx=ComputeContext.local())
# qos: generous enough that the sequential checks never shed, small
# enough that the burst at the end reliably trips 429s; no stale cache
# (a cache hit would turn the asserted 429 into a degraded 200)
server, service = create_query_server(
    variant, host="127.0.0.1", port=0, ctx=ComputeContext.local(),
    slos=["p99=50ms:99.9", "availability=99.9"],
    qos="rps=2,burst=8",
)
server.start()
with open(sys.argv[1] + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(sys.argv[1] + ".tmp", sys.argv[1])  # atomic publish
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
PY
SERVER_PID=$!

echo "waiting for server to boot (train + deploy)..."
for _ in $(seq 1 240); do
    [ -s "$PORT_FILE" ] && break
    kill -0 "$SERVER_PID" 2>/dev/null || {
        echo "FAIL: server process died during boot" >&2; exit 1; }
    sleep 0.5
done
[ -s "$PORT_FILE" ] || { echo "FAIL: server never published its port" >&2; exit 1; }
PORT="$(cat "$PORT_FILE")"
BASE="http://127.0.0.1:$PORT"
echo "server up on :$PORT"

check_json() {  # 200 + parseable JSON
    local path="$1"
    curl -fsS --max-time 10 "$BASE$path" | python -m json.tool >/dev/null \
        || fail "$path did not return 200 + valid JSON"
    echo "ok   $path"
}

# live traffic first, so /logs.json, /metrics and /slo.json report a
# real request (not just empty rings)
curl -fsS --max-time 30 -X POST -H 'Content-Type: application/json' \
    -d '{"user": "u1", "num": 3}' "$BASE/queries.json" \
    | python -m json.tool >/dev/null || fail "/queries.json round trip"
echo "ok   /queries.json"

check_json /healthz
check_json /readyz
check_json /logs.json
check_json "/logs.json?level=info&n=50"
check_json /slo.json
check_json /traces.json
check_json /stats.json
check_json /qos.json

# /qos.json must reflect the deployed admission policy
curl -fsS --max-time 10 "$BASE/qos.json" | python -c '
import json, sys
body = json.load(sys.stdin)
assert body["enabled"] is True, body
assert body["policy"]["rps"] == 2, body["policy"]
assert "shed" in body and "bucket" in body, body
' || fail "/qos.json missing admission-control state"
echo "ok   /qos.json policy"

# /slo.json must carry both declared objectives with burn-rate fields
curl -fsS --max-time 10 "$BASE/slo.json" | python -c '
import json, sys
body = json.load(sys.stdin)
names = {s["name"] for s in body["slos"]}
assert {"latency_p99", "availability"} <= names, names
for s in body["slos"]:
    assert "burnRates" in s and "errorBudgetRemaining" in s, s
' || fail "/slo.json missing declared objectives"
echo "ok   /slo.json objectives"

# /metrics must be Prometheus text with the core families present
METRICS="$(curl -fsS --max-time 10 "$BASE/metrics")"
for family in \
    '# TYPE pio_tpu_queries_total counter' \
    '# TYPE pio_tpu_request_seconds histogram' \
    '# TYPE pio_tpu_slo_error_budget_remaining gauge' \
    '# TYPE pio_tpu_log_messages_total counter'; do
    grep -qF "$family" <<<"$METRICS" || fail "/metrics missing '$family'"
done
echo "ok   /metrics exposition"

# parameter validation: negative n must be a 400, not a silent default
STATUS="$(curl -s -o /dev/null -w '%{http_code}' --max-time 10 "$BASE/logs.json?n=-5")"
[ "$STATUS" = 400 ] || fail "/logs.json?n=-5 returned $STATUS, want 400"
echo "ok   /logs.json?n=-5 -> 400"

# ------------------------------------------------- latency attribution
# an X-Pio-Trace we send must be adopted verbatim and echoed back, and
# the adopted trace's full waterfall must be retrievable by id
HDR="$(curl -fsS --max-time 10 -D - -o /dev/null \
    -X POST -H 'Content-Type: application/json' \
    -H 'X-Pio-Trace: smoke-trace-1' \
    -d '{"user": "u1", "num": 3}' "$BASE/queries.json")" \
    || fail "traced /queries.json POST failed"
grep -qi '^X-Pio-Trace: smoke-trace-1' <<<"$HDR" \
    || fail "response did not echo the adopted trace id (headers: $HDR)"
curl -fsS --max-time 10 "$BASE/traces.json?id=smoke-trace-1" | python -c '
import json, sys
body = json.load(sys.stdin)
stages = {s["stage"] for t in body["traces"] for s in t["spans"]}
assert {"accept", "parse", "execute", "write"} <= stages, stages
' || fail "/traces.json?id= did not return the adopted trace's waterfall"
echo "ok   X-Pio-Trace adopted + waterfall retrievable by id"

# the hot-path budget must attribute (stage sum ≈ e2e): the declared
# bar is >=95% on the bench's steady-state load; this smoke run is a
# cold server, so warm the average over a few extra requests (a single
# cold request's scheduling noise can dominate its ~1 ms budget) and
# gate at 80% — enough to catch a stage that silently stopped reporting
for _ in 1 2 3 4 5 6; do
    curl -fsS --max-time 10 -o /dev/null -X POST \
        -H 'Content-Type: application/json' \
        -d '{"user": "u1", "num": 3}' "$BASE/queries.json" \
        || fail "hotpath warm-up POST failed"
done
sleep 0.3  # e2e lands in the post-write hook; let the last one settle
curl -fsS --max-time 10 "$BASE/debug/hotpath.json" | python -c '
import json, sys
body = json.load(sys.stdin)
assert body["requestCount"] >= 5, body
stages = {s["stage"] for s in body["stages"]}
assert {"accept", "admit", "parse", "queue", "execute", "serialize",
        "write"} <= stages, stages
frac = body["attributedFraction"]
assert frac is not None and frac >= 0.80, (
    f"hot-path stages attribute only {frac!r} of the e2e average "
    f"(want >= 0.80): {json.dumps(body, indent=1)[:2000]}")
' || fail "/debug/hotpath.json stage sum does not match e2e latency"
echo "ok   /debug/hotpath.json attributes >=80% of e2e latency"

# admission control: rapid-fire past the rps=2,burst=8 budget (LAST, so
# drained tokens can't starve the checks above) and require at least one
# 429 carrying a Retry-After hint
SHED_HEADERS="$WORKDIR/shed-headers"
GOT_429=0
for _ in $(seq 1 25); do
    STATUS="$(curl -s -o /dev/null -D "$SHED_HEADERS" -w '%{http_code}' \
        --max-time 10 -X POST -H 'Content-Type: application/json' \
        -d '{"user": "u1", "num": 3}' "$BASE/queries.json")"
    if [ "$STATUS" = 429 ]; then GOT_429=1; break; fi
done
[ "$GOT_429" = 1 ] || fail "burst of 25 queries never rate-limited (no 429)"
grep -qi '^Retry-After:' "$SHED_HEADERS" \
    || fail "429 response missing Retry-After header"
echo "ok   burst -> 429 + Retry-After"

# ...and the shed must be accounted on /qos.json and /metrics
curl -fsS --max-time 10 "$BASE/qos.json" | python -c '
import json, sys
body = json.load(sys.stdin)
assert body["shed"]["rate_limit"] >= 1, body["shed"]
' || fail "/qos.json did not count the rate_limit shed"
# capture, THEN grep: grep -q exits at first match and a direct pipe
# would hand curl a SIGPIPE (exit 23) under pipefail once the /metrics
# body outgrows the pipe buffer
SHED_METRICS="$(curl -fsS --max-time 10 "$BASE/metrics")"
grep -q 'pio_tpu_qos_shed_total{.*reason="rate_limit"' <<<"$SHED_METRICS" \
    || fail "/metrics missing pio_tpu_qos_shed_total rate_limit sample"
echo "ok   shed accounted in /qos.json + /metrics"

# ------------------------------------------------------------------ chaos
# Fault injection: boot an EVENT server over sqlite with a low-rate
# latency+error spec armed (10 ms latency on every group-commit flush,
# 10 % injected errors on the sqlite commit). Every POST must still come
# back 201 — group commit's solo retry plus the server's retrying()
# wrapper absorb the injected errors, so no 5xx may leak — and the
# injections must be visible on /faults.json and /metrics.
CHAOS_PORT_FILE="$WORKDIR/chaos-port"
CHAOS_KEY_FILE="$WORKDIR/chaos-key"

# Before arming the spec, cross-check its point names against the lint
# inventory of failpoint() call sites — a renamed point would otherwise
# silently arm nothing and the chaos stage would stop testing anything.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = json.load(sys.stdin)["failpoints"]
wanted = ["groupcommit.flush.sqlite", "storage.sqlite.commit"]
for name in wanted:
    for fp in inv:
        point = fp["point"]
        # dynamic points carry their static f-string prefix
        if point == name or (fp["dynamic"] and name.startswith(point)):
            break
    else:
        raise SystemExit(
            f"chaos spec targets {name!r} but no failpoint() call site "
            f"matches it — inventory: {sorted(f['point'] for f in inv)}")
' || fail "chaos spec references a failpoint that no longer exists"
echo "ok   chaos spec failpoints exist in the lint inventory"
PIO_TPU_FAULTS='groupcommit.flush.sqlite=latency:10ms,storage.sqlite.commit=error:0.1' \
python - "$CHAOS_PORT_FILE" "$CHAOS_KEY_FILE" <<'PY' &
import os
import signal
import sys

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_SOURCES_SQ_TYPE"] = "sqlite"
os.environ["PIO_STORAGE_SOURCES_SQ_PATH"] = os.path.join(
    os.environ["PIO_TPU_HOME"], "chaos.db")
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "SQ"

from pio_tpu.server import create_event_server
from pio_tpu.storage import AccessKey, App, Storage

app_id = Storage.get_meta_data_apps().insert(App(0, "chaos"))
key = Storage.get_meta_data_access_keys().insert(AccessKey("", app_id))
server = create_event_server(host="127.0.0.1", port=0).start()
with open(sys.argv[2], "w") as f:
    f.write(key)
with open(sys.argv[1] + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(sys.argv[1] + ".tmp", sys.argv[1])  # atomic publish
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
PY
CHAOS_PID=$!

echo "waiting for chaos event server..."
for _ in $(seq 1 120); do
    [ -s "$CHAOS_PORT_FILE" ] && break
    kill -0 "$CHAOS_PID" 2>/dev/null || {
        echo "FAIL: chaos event server died during boot" >&2; exit 1; }
    sleep 0.5
done
[ -s "$CHAOS_PORT_FILE" ] || fail "chaos event server never published its port"
CBASE="http://127.0.0.1:$(cat "$CHAOS_PORT_FILE")"
CKEY="$(cat "$CHAOS_KEY_FILE")"
echo "chaos event server up, faults armed"

for i in $(seq 1 30); do
    STATUS="$(curl -s -o /dev/null -w '%{http_code}' --max-time 15 \
        -X POST -H 'Content-Type: application/json' \
        -d "{\"event\": \"chaos\", \"entityType\": \"user\",
             \"entityId\": \"u$i\", \"targetEntityType\": \"item\",
             \"targetEntityId\": \"i$i\",
             \"eventTime\": \"2026-03-01T10:00:00Z\"}" \
        "$CBASE/events.json?accessKey=$CKEY")"
    [ "$STATUS" = 201 ] \
        || fail "chaos POST $i returned $STATUS, want 201 (injected fault leaked past the retry layer)"
done
echo "ok   30/30 event POSTs -> 201 under injected faults"

# /faults.json must report the armed spec and at least one trigger (the
# latency rule fires on every group-commit flush, so >= 1 is guaranteed)
curl -fsS --max-time 10 "$CBASE/faults.json" | python -c '
import json, sys
body = json.load(sys.stdin)
assert body["enabled"] is True, body
assert sum(t["count"] for t in body["triggered"]) >= 1, body
' || fail "/faults.json missing armed spec / trigger counts"
CHAOS_METRICS="$(curl -fsS --max-time 10 "$CBASE/metrics")"
grep -q 'pio_tpu_fault_triggered_total{' <<<"$CHAOS_METRICS" \
    || fail "/metrics missing pio_tpu_fault_triggered_total sample"
echo "ok   injections visible on /faults.json + /metrics"

# ----------------------------------- chaos v2: partlog leader failover
# ISSUE 9: a 3-partition replicated event server at commit durability
# must lose ZERO acknowledged writes when its leader is SIGKILLed
# mid-ingest — a 201 is only sent after >= min_acks followers fsynced
# the record, so the longest-verified-prefix promotion serves every
# acked event. The drill also proves /storage.json reports the live
# topology and the partlog/repl metric families are present.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"partlog.append.before_write", "repl.send", "repl.ack"}
missing = need - inv
assert not missing, f"partlog/repl failpoints missing from inventory: {missing}"
' || fail "partlog/repl failpoints missing from --dump-failpoints"
echo "ok   partlog/repl failpoints in lint inventory"

FAILOVER_STAGE="$WORKDIR/failover_stage.py"
cat > "$FAILOVER_STAGE" <<'PY'
"""Smoke stage: partitioned-log leader failover under SIGKILL.

Boots two in-process follower replicas and an EVENT server subprocess
over a 3-partition ``partlog`` at ``commit`` durability (a 201 is sent
only after a follower fsynced the record). A writer thread ingests
continuously; once enough writes are acked the leader is SIGKILLed
mid-ingest, the followers are promoted by longest verified prefix, and
the promoted log must serve EVERY acked write — zero acked-write loss.
"""
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

WORKDIR = sys.argv[1]

from pio_tpu.storage.partlog import failover
from pio_tpu.storage.partlog.partitioned import PartitionedEventLog
from pio_tpu.storage.partlog.replication import FollowerServer

froot1 = os.path.join(WORKDIR, "failover-f1")
froot2 = os.path.join(WORKDIR, "failover-f2")
f1 = FollowerServer(froot1)
f2 = FollowerServer(froot2)

leader_root = os.path.join(WORKDIR, "failover-leader")
port_file = os.path.join(WORKDIR, "failover-port")
info_file = os.path.join(WORKDIR, "failover-info")

LEADER_SRC = r'''
import json, os, signal, sys
from pio_tpu.server import create_event_server
from pio_tpu.storage import AccessKey, App, Storage

app_id = Storage.get_meta_data_apps().insert(App(0, "failover"))
key = Storage.get_meta_data_access_keys().insert(AccessKey("", app_id))
server = create_event_server(host="127.0.0.1", port=0).start()
info_file, port_file = sys.argv[1], sys.argv[2]
with open(info_file, "w") as f:
    json.dump({"key": key, "app_id": app_id}, f)
with open(port_file + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(port_file + ".tmp", port_file)  # atomic publish
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
'''

env = dict(os.environ)
env.update({
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PL",
    "PIO_STORAGE_SOURCES_PL_TYPE": "partlog",
    "PIO_STORAGE_SOURCES_PL_PATH": leader_root,
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    "PIO_TPU_PARTLOG_PARTITIONS": "3",
    "PIO_TPU_PARTLOG_REPLICAS": f"127.0.0.1:{f1.port},127.0.0.1:{f2.port}",
    "PIO_TPU_DURABILITY": "commit",
})
proc = subprocess.Popen(
    [sys.executable, "-c", LEADER_SRC, info_file, port_file], env=env)


def _cleanup():
    # a failed assertion must not leave the leader (sigwait) or the
    # follower accept loops holding the stage open
    stop_writer.set()
    if proc.poll() is None:
        proc.kill()
        proc.wait()
    f1.stop()
    f2.stop()


deadline = time.time() + 60
while not os.path.exists(port_file):
    if proc.poll() is not None:
        raise SystemExit("leader event server died during boot")
    if time.time() > deadline:
        proc.kill()
        raise SystemExit("leader event server never published its port")
    time.sleep(0.2)
with open(port_file) as f:
    base = "http://127.0.0.1:" + f.read().strip()
with open(info_file) as f:
    info = json.load(f)


def get(path):
    with urllib.request.urlopen(base + path, timeout=10) as r:
        return r.read().decode("utf-8")


acked = set()
stop_writer = threading.Event()


def writer():
    i = 0
    while not stop_writer.is_set():
        i += 1
        body = json.dumps({
            "event": "chaos", "entityType": "user", "entityId": f"u{i}",
            "properties": {"seq": i},
            "eventTime": "2026-03-01T10:00:00Z",
        }).encode("utf-8")
        req = urllib.request.Request(
            base + "/events.json?accessKey=" + info["key"],
            data=body, headers={"Content-Type": "application/json"})
        try:
            with urllib.request.urlopen(req, timeout=15) as r:
                if r.status == 201:
                    acked.add(f"u{i}")
        except Exception:
            return  # leader is gone: the in-flight write was never acked


t = threading.Thread(target=writer, daemon=True)
t.start()
try:
    deadline = time.time() + 60
    while len(acked) < 15:
        if time.time() > deadline:
            raise SystemExit(f"only {len(acked)} writes acked in 60s")
        time.sleep(0.05)

    # the outside view while the leader is up: topology + repl metrics
    topo = json.loads(get("/storage.json"))
    assert topo["backend"] == "partlog", topo
    assert topo["role"] == "leader" and topo["partitions"] == 3, topo
    assert len(topo["partition_detail"]) == 3, topo
    repl = topo["replication"]
    assert repl is not None and repl["min_acks"] >= 1, repl
    assert len(repl["followers"]) == 2, repl
    metrics = get("/metrics")
    for fam in ("pio_tpu_partlog_appends_total", "pio_tpu_repl_acks_total"):
        assert fam + "{" in metrics, f"/metrics missing {fam}"

    # mid-ingest SIGKILL: the writer thread is still posting
    os.kill(proc.pid, signal.SIGKILL)
    proc.wait()
    stop_writer.set()
    t.join(timeout=30)
    n_acked = len(acked)
finally:
    _cleanup()

promoted_root = os.path.join(WORKDIR, "failover-promoted")
report = failover.promote([froot1, froot2], promoted_root)
assert report["partitions"] == 3, report

log = PartitionedEventLog(promoted_root)
try:
    got = {e.entity_id for e in log.find(info["app_id"])}
finally:
    log.close()
lost = acked - got
assert not lost, (
    f"promoted follower lost {len(lost)} acked writes: {sorted(lost)[:5]}")
print(f"failover stage: {n_acked} acked writes, 0 lost after promotion "
      f"({len(got)} records served by the promoted root)")
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$FAILOVER_STAGE" "$WORKDIR" \
    || fail "partlog failover stage (acked-write loss / topology assertions)"
echo "ok   partlog failover: leader SIGKILLed mid-ingest, zero acked writes lost"

# -------------------------------------------------- pooled batch lane
# ISSUE 7: a pooled server with the shape-bucket cache warmed and the
# cross-worker batch lane armed must keep the micro-batcher engaged
# under concurrent load (mode != "off") and never retrace a bucket in
# steady state (the retrace counter stays flat across the timed
# window). The driver is a real temp FILE, not a heredoc on stdin:
# the pool's spawn context re-imports __main__ in every worker
# (__mp_main__), which needs an importable path — the module guards
# its body with __name__ == "__main__" so workers import it inertly.
POOL_STAGE="$WORKDIR/pool_stage.py"
cat > "$POOL_STAGE" <<'PY'
"""Smoke stage: pooled serving with shape buckets + the batch lane.

Boots a 2-worker SO_REUSEPORT pool (worker 0 designated device owner so
the lane arms), drives concurrent load, then asserts on the OUTSIDE
view (/metrics pool-wide sums, /stats.json):

- the bucket retrace counter is FLAT across the steady-state window
  (every batch shape was served by a warmed executable),
- the batch lane actually moved traffic (drained counter > 0),
- the micro-batcher did not latch off (``mode != "off"``).
"""
import datetime as dt
import json
import os
import threading
import time
import urllib.request


def _post(base, body, timeout=30):
    req = urllib.request.Request(
        base + "/queries.json",
        data=json.dumps(body).encode("utf-8"),
        headers={"Content-Type": "application/json"},
    )
    with urllib.request.urlopen(req, timeout=timeout) as r:
        return json.loads(r.read().decode("utf-8"))


def _get(base, path, timeout=10):
    with urllib.request.urlopen(base + path, timeout=timeout) as r:
        return r.read().decode("utf-8")


def _counter_total(metrics_text, name):
    """Sum every sample of one counter family in Prometheus text (the
    scrape already sums worker stripes; this folds label cells)."""
    total = 0.0
    for line in metrics_text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            total += float(line.rsplit(" ", 1)[1])
    return total


def _wait_every_worker(base, n_workers, timeout=240.0):
    """Until every pool worker has answered on the shared port.
    ``wait_ready`` vouches for worker 0 alone; a sibling that is not
    listening yet is sent nothing by the kernel, so a load driven now
    would be served by worker 0 alone and the lane would carry nothing."""
    seen = set()
    deadline = time.monotonic() + timeout
    while len(seen) < n_workers:
        if time.monotonic() > deadline:
            raise SystemExit(
                f"only workers {sorted(seen)} of {n_workers} answered "
                f"/stats.json within {timeout:.0f} s")
        try:
            seen.add(json.loads(_get(base, "/stats.json"))["worker"])
        except (OSError, KeyError, ValueError):  # refused, 503, no index yet
            pass
        time.sleep(0.05)


def _drive(base, n_threads, n_each, retry=False):
    errs = []

    def run(t):
        for q in range(n_each):
            body = {"user": "u%d" % ((t * 31 + q) % 8), "num": 3}
            for attempt in range(40 if retry else 1):
                try:
                    got = _post(base, body)
                    assert "itemScores" in got, got
                    break
                except Exception as exc:  # 503 while a worker warms up
                    if not retry or attempt == 39:
                        errs.append(exc)
                        return
                    time.sleep(0.5)

    threads = [
        threading.Thread(target=run, args=(t,)) for t in range(n_threads)
    ]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errs:
        raise SystemExit(f"pool load failed: {errs[:3]}")


def main():
    os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SQ"
    os.environ["PIO_STORAGE_SOURCES_SQ_TYPE"] = "sqlite"
    os.environ["PIO_STORAGE_SOURCES_SQ_PATH"] = os.path.join(
        os.environ["PIO_TPU_HOME"], "pool.db")
    os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "SQ"
    os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "SQ"
    # batching on (the micro-batcher + the warmup sweep key off this);
    # a short ladder keeps the per-worker CPU warmup sweep quick
    os.environ["PIO_TPU_SERVE_MICROBATCH_US"] = "1500"
    os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
    os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4,8"

    import pio_tpu.templates  # noqa: F401  (registers the factory)
    from pio_tpu.controller import ComputeContext
    from pio_tpu.data import Event
    from pio_tpu.server.worker_pool import ServingPool
    from pio_tpu.storage import App, Storage
    from pio_tpu.workflow import build_engine, run_train, variant_from_dict

    app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-pool"))
    le = Storage.get_levents()
    t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
    for u in range(8):
        for i in range(6):
            in_block = (u < 4) == (i < 3)
            le.insert(
                Event("rate", "user", f"u{u}", "item", f"i{i}",
                      properties={"rating": 5.0 if in_block else 1.0},
                      event_time=t0),
                app_id,
            )
    variant = variant_from_dict({
        "id": "smoke-pool-rec",
        "engineFactory": "templates.recommendation",
        "datasource": {"params": {"app_name": "smoke-pool"}},
        "algorithms": [{"name": "als", "params": {
            "rank": 4, "num_iterations": 4, "lambda_": 0.1}}],
    })
    engine, ep = build_engine(variant)
    run_train(engine, ep, variant, ctx=ComputeContext.local())

    pool = ServingPool(
        variant, host="127.0.0.1", port=0, n_workers=2,
        device_worker=True,
    )
    pool.start()
    try:
        pool.wait_ready(timeout=240.0)
        base = f"http://127.0.0.1:{pool.port}"
        _wait_every_worker(base, 2)
        # settle round: both workers listen now, but a worker answers
        # 503 until it is deployed + warmed, so retry those; any cold
        # compile (first num=3 top-k) lands here, outside the timed
        # window
        _drive(base, 8, 5, retry=True)
        retrace_before = _counter_total(
            _get(base, "/metrics"), "pio_tpu_bucket_retrace_total")
        # steady state: 16 concurrent clients across both workers
        _drive(base, 16, 10)
        metrics = _get(base, "/metrics")
        retrace_after = _counter_total(
            metrics, "pio_tpu_bucket_retrace_total")
        assert retrace_after == retrace_before, (
            f"bucket retraces moved {retrace_before} -> {retrace_after} "
            f"under steady-state load: a batch shape escaped the "
            f"warmed ladder")
        drained = _counter_total(
            metrics, "pio_tpu_batchlane_drained_total")
        assert drained >= 1, (
            f"batch lane never drained a request (drained={drained}); "
            f"pool queries are not aggregating")
        # the micro-batcher must not have latched off; sample stats over
        # several connections (the kernel picks the answering worker)
        modes = {}
        for _ in range(12):
            st = json.loads(_get(base, "/stats.json"))
            mb = st.get("microbatch")
            if mb is not None:
                modes[st.get("worker")] = mb["mode"]
        assert modes, "no worker reported micro-batch stats"
        assert "off" not in modes.values(), (
            f"micro-batcher latched off under pooled load: {modes}")
        print(f"pool stage: modes={modes} drained={int(drained)} "
              f"retraces={int(retrace_after)}")
    finally:
        pool.stop()


if __name__ == "__main__":
    main()
PY
# PYTHONPATH: the driver lives in $WORKDIR, so sys.path[0] is /tmp —
# point it (and the spawned pool workers, which inherit the env) at
# this checkout
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$POOL_STAGE" \
    || fail "pooled batch-lane stage (mode/retrace/lane assertions)"
echo "ok   pooled serving: micro-batcher engaged, retraces flat, lane drained"

# ------------------------------------------ device-resident serving
# ISSUE 8: the resident-scorer failpoints must be dump-visible (a chaos
# spec targeting them must arm something), then a classification server
# with residency forced on and the int8 query wire must serve a steady
# window where the h2d counter grows by AT MOST the int8 payload per
# request (1 byte/feature — the params never re-ship), the bucket
# retrace counter stays flat, and the donation hit rate holds >= 0.95.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"scorer.h2d.ship", "scorer.donate.dispatch"}
missing = need - inv
assert not missing, f"resident failpoints missing from inventory: {missing}"
' || fail "scorer.h2d/scorer.donate failpoints missing from --dump-failpoints"
echo "ok   scorer.h2d/scorer.donate failpoints in lint inventory"

python - <<'PY' || fail "device-resident stage (h2d/retrace/donation assertions)"
"""Smoke stage: device-resident serving on the int8 query wire.

Boots a classification server with ``PIO_TPU_DEVICE_RESIDENT=1`` and
``PIO_TPU_SERVE_WIRE=int8``, warms it, then drives a steady window and
asserts from the OUTSIDE view (/metrics, /stats.json) that the wire is
actually thin: h2d bytes grow by <= 1 byte/feature/request, zero
retraces, donation hit rate >= 0.95, and every prediction is right.
"""
import datetime as dt
import json
import os
import urllib.request

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
os.environ["PIO_TPU_DEVICE_RESIDENT"] = "1"
os.environ["PIO_TPU_SERVE_WIRE"] = "int8"
os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-res"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
PLANS = ("basic", "premium", "pro")
n = 0
for hot, plan in enumerate(PLANS):
    for _ in range(8):
        props = {f"attr{j}": (7 if j == hot else 1) for j in range(3)}
        props["plan"] = plan
        le.insert(
            Event("$set", "user", f"u{n}", properties=props,
                  event_time=t0 + dt.timedelta(minutes=n)),
            app_id,
        )
        n += 1
variant = variant_from_dict({
    "id": "smoke-resident",
    "engineFactory": "templates.classification",
    "datasource": {"params": {"app_name": "smoke-res"}},
    "algorithms": [{"name": "logreg", "params": {}}],
})
engine, ep = build_engine(variant)
ctx = ComputeContext.local()
run_train(engine, ep, variant, ctx=ctx)
server, _service = create_query_server(
    variant, host="127.0.0.1", port=0, ctx=ctx
)
server.start()
try:
    base = f"http://127.0.0.1:{server.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/queries.json",
            data=json.dumps(body).encode("utf-8"),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode("utf-8"))

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return r.read().decode("utf-8")

    def counter(text, name):
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name + "{") or line.startswith(name + " "):
                total += float(line.rsplit(" ", 1)[1])
        return total

    got = post({"attrs": [9.0, 1.0, 1.0]})  # warm route + wire
    assert got.get("label") == "basic", got
    m0 = get("/metrics")
    h2d0 = counter(m0, "pio_tpu_serving_h2d_bytes_total")
    retr0 = counter(m0, "pio_tpu_bucket_retrace_total")
    N, D = 40, 3
    for q in range(N):
        hot = q % 3
        got = post({"attrs": [9.0 if j == hot else 1.0 for j in range(3)]})
        assert got.get("label") == PLANS[hot], (q, got)
    m1 = get("/metrics")
    h2d = counter(m1, "pio_tpu_serving_h2d_bytes_total") - h2d0
    retr = counter(m1, "pio_tpu_bucket_retrace_total") - retr0
    assert 0 < h2d <= N * D, (
        f"h2d grew {h2d} bytes over {N} requests on the int8 wire "
        f"(want (0, {N * D}]: 1 byte/feature, params never re-ship)")
    assert retr == 0, f"bucket retraces moved by {retr} in steady state"
    res = json.loads(get("/stats.json"))["residency"]
    assert res["enabled"] and res["paramBytes"] > 0, res
    sc = res["scorers"][0]
    assert sc["wire"] == "int8", sc
    assert sc["donation"]["hitRate"] >= 0.95, sc["donation"]
    print(f"resident stage: h2d={int(h2d)}B/{N} reqs retraces={int(retr)} "
          f"donationHitRate={sc['donation']['hitRate']}")
finally:
    server.stop()
PY
echo "ok   device-resident serving: int8 wire thin, retraces flat, donations hit"

# ------------------------------------------------ device telemetry plane
# ISSUE 17: the devicewatch failpoints must be dump-visible, then a
# resident server's /device.json must book real ledger bytes against
# the budget, hold the compile-attribution counters FLAT over a steady
# window AND across a hot swap (while the generation bumps), release
# bytes on scorer retirement (peak survives), and a dashboard pointed
# at the server must render /devices.html from one scrape.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"devicewatch.sample", "devicewatch.payload"}
missing = need - inv
assert not missing, f"devicewatch failpoints missing from inventory: {missing}"
' || fail "devicewatch failpoints missing from --dump-failpoints"
echo "ok   devicewatch failpoints in lint inventory"

python - <<'PY' || fail "device telemetry stage (bytes/compile/generation assertions)"
"""Smoke stage: the device telemetry plane over a deploy -> steady ->
hot-swap -> retire walk, asserted from the OUTSIDE view (/device.json,
/metrics, /devices.html)."""
import datetime as dt
import json
import os
import urllib.request

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
os.environ["PIO_TPU_DEVICE_RESIDENT"] = "1"
os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4"
os.environ["PIO_TPU_DEVICE_BUDGET_BYTES"] = str(64 * 1024 * 1024)
os.environ["PIO_TPU_DEVICEWATCH_INTERVAL_S"] = "0.2"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.server.dashboard import create_dashboard
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-dev"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
PLANS = ("basic", "premium", "pro")
n = 0
for hot, plan in enumerate(PLANS):
    for _ in range(8):
        props = {f"attr{j}": (7 if j == hot else 1) for j in range(3)}
        props["plan"] = plan
        le.insert(
            Event("$set", "user", f"u{n}", properties=props,
                  event_time=t0 + dt.timedelta(minutes=n)),
            app_id,
        )
        n += 1
variant = variant_from_dict({
    "id": "smoke-devwatch",
    "engineFactory": "templates.classification",
    "datasource": {"params": {"app_name": "smoke-dev"}},
    "algorithms": [{"name": "logreg", "params": {}}],
})
engine, ep = build_engine(variant)
ctx = ComputeContext.local()
run_train(engine, ep, variant, ctx=ctx)
server, service = create_query_server(
    variant, host="127.0.0.1", port=0, ctx=ctx
)
server.start()
dash = None
try:
    base = f"http://127.0.0.1:{server.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/queries.json",
            data=json.dumps(body).encode("utf-8"),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode("utf-8"))

    def get(path, b=None):
        with urllib.request.urlopen((b or base) + path, timeout=10) as r:
            return r.read().decode("utf-8")

    d0 = json.loads(get("/device.json"))
    assert d0["generation"] == 1, d0["generation"]
    assert d0["ledger"]["totalBytes"] > 0, "deploy booked no ledger bytes"
    assert d0["ledger"]["byCategory"]["resident"] > 0, d0["ledger"]
    assert d0["budgetBytes"] == 64 * 1024 * 1024, d0["budgetBytes"]
    assert 0 < d0["headroomBytes"] < d0["budgetBytes"], d0["headroomBytes"]
    sites = d0["compiles"]["sites"]
    assert sites["bucket_warmup"]["count"] == 3, sites
    c0 = d0["compiles"]["total"]

    # steady window: compile counters must not move
    for q in range(30):
        hot = q % 3
        got = post({"attrs": [9.0 if j == hot else 1.0 for j in range(3)]})
        assert got.get("label") == PLANS[hot], (q, got)
    d1 = json.loads(get("/device.json"))
    assert d1["compiles"]["total"] == c0, (
        f"compiles moved {c0} -> {d1['compiles']['total']} in steady state")

    # hot swap: generation bumps, the re-warm over the unchanged bucket
    # ladder hits the global jit cache and must NOT be recounted
    service._load(None)
    d2 = json.loads(get("/device.json"))
    assert d2["generation"] == 2, d2["generation"]
    assert d2["compiles"]["total"] == c0, (
        f"hot-swap re-warm recounted compiles: {c0} -> "
        f"{d2['compiles']['total']}")
    assert d2["ledger"]["byCategory"]["resident"] > 0, d2["ledger"]
    live_bytes = d2["ledger"]["totalBytes"]

    # retire: resident + donated bytes fall to zero, the peak survives
    for sc in list(service._resident):
        sc.retire()
    d3 = json.loads(get("/device.json"))
    cats = d3["ledger"]["byCategory"]
    assert cats.get("resident", 0) == 0, cats
    assert cats.get("donated", 0) == 0, cats
    assert d3["ledger"]["totalBytes"] < live_bytes
    peak = d3["devices"][0]["peakBytes"]
    assert peak >= live_bytes, (peak, live_bytes)

    m = get("/metrics")
    for fam in ("pio_tpu_device_bytes_in_use", "pio_tpu_device_peak_bytes",
                "pio_tpu_device_budget_headroom_bytes",
                "pio_tpu_xla_compile_total"):
        assert fam in m, f"{fam} missing from /metrics"

    # dashboard renders the plane from one scrape
    dash = create_dashboard(host="127.0.0.1", port=0, query_url=base)
    dash.start()
    page = get("/devices.html", b=f"http://127.0.0.1:{dash.port}")
    assert "scrape failed" not in page, page[:400]
    assert "bucket_warmup" in page and "HBM (MiB)" in page, page[:400]
    print(f"device stage: ledger {live_bytes}B live -> "
          f"{d3['ledger']['totalBytes']}B retired, peak {peak}B, "
          f"compiles {c0} flat across steady+swap, gen 1->2")
finally:
    if dash is not None:
        dash.stop()
    server.stop()
PY
echo "ok   device telemetry: bytes rise/fall, compiles flat, /devices.html renders"

# ------------------------------------------------ evloop HTTP front
# ISSUE 13: the selector-based front must hold the threaded baseline
# on pooled keep-alive load, keep /debug/hotpath.json attribution >= 95%, and
# the packed int8 wire must take the zero-copy fast path with exact
# JSON parity.
EVFRONT_STAGE="$WORKDIR/evfront_stage.py"
cat > "$EVFRONT_STAGE" <<'PY'
"""Smoke stage: the evloop HTTP front + packed int8 wire vs threaded.

Boots the SAME trained classification engine behind both fronts
(``PIO_TPU_HTTP_FRONT``) and drives each with a multiplexed raw-socket
client over 16 keep-alive connections — the threaded baseline serves
the JSON wire, the evloop front serves the packed int8 wire (the
deployment the tentpole ships). Asserts from the OUTSIDE view:

- evloop QPS >= the threaded baseline (this gate catches a regression;
  no serving cell of the benchmark measures the ratio yet),
- /debug/hotpath.json ``attributedFraction`` >= 0.95 on the evloop
  front under steady-state load,
- a packed ``application/x-pio-query-i8`` POST answers byte-for-byte
  parity with the JSON wire and takes the zero-copy fast path
  (``pio_tpu_http_parse_fastpath_total`` moves).
"""
import datetime as dt
import json
import os
import selectors
import socket
import time

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
os.environ["PIO_TPU_DEVICE_RESIDENT"] = "1"
os.environ["PIO_TPU_SERVE_WIRE"] = "int8"
os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.server.http import PACKED_QUERY_CONTENT_TYPE
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-evfront"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
PLANS = ("basic", "premium", "pro")
n = 0
for hot, plan in enumerate(PLANS):
    for _ in range(8):
        props = {f"attr{j}": (7 if j == hot else 1) for j in range(3)}
        props["plan"] = plan
        le.insert(
            Event("$set", "user", f"u{n}", properties=props,
                  event_time=t0 + dt.timedelta(minutes=n)),
            app_id,
        )
        n += 1
variant = variant_from_dict({
    "id": "smoke-evfront",
    "engineFactory": "templates.classification",
    "datasource": {"params": {"app_name": "smoke-evfront"}},
    "algorithms": [{"name": "logreg", "params": {}}],
})
engine, ep = build_engine(variant)
ctx = ComputeContext.local()
run_train(engine, ep, variant, ctx=ctx)


def mk_req(payload, ctype):
    return (b"POST /queries.json HTTP/1.1\r\nHost: x\r\n"
            b"Content-Type: " + ctype.encode("latin-1") + b"\r\n"
            b"Content-Length: " + str(len(payload)).encode() +
            b"\r\n\r\n" + payload)


def _count_responses(buf, on_body=None):
    """Pop complete Content-Length-framed responses off ``buf``."""
    got = 0
    while True:
        he = buf.find(b"\r\n\r\n")
        if he < 0:
            return got
        cl = 0
        for hline in bytes(buf[:he]).lower().split(b"\r\n"):
            if hline.startswith(b"content-length:"):
                cl = int(hline.split(b":", 1)[1])
        if len(buf) < he + 4 + cl:
            return got
        if on_body is not None:
            on_body(bytes(buf[he + 4:he + 4 + cl]))
        del buf[:he + 4 + cl]
        got += 1


def drive(port, req, n_conns, total):
    """One outstanding request per keep-alive connection, multiplexed
    in ONE client thread (a thread-per-connection client would cost
    more GIL time than either server front under test)."""
    sel = selectors.DefaultSelector()
    socks = []
    for _ in range(n_conns):
        s = socket.create_connection(("127.0.0.1", port))
        s.setblocking(False)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        socks.append(s)
        sel.register(s, selectors.EVENT_READ, bytearray())
    sent = done = 0
    start = time.monotonic()
    for s in socks:
        s.sendall(req)
        sent += 1
    while done < total:
        for key, _ in sel.select(10):
            s, buf = key.fileobj, key.data
            chunk = s.recv(65536)
            if not chunk:
                raise SystemExit("server closed a keep-alive connection")
            buf += chunk
            for _ in range(_count_responses(buf)):
                done += 1
                if sent < total:
                    s.sendall(req)
                    sent += 1
    took = time.monotonic() - start
    for s in socks:
        sel.unregister(s)
        s.close()
    return total / took


def one(port, method, path, payload=None, ctype=None):
    s = socket.create_connection(("127.0.0.1", port))
    if payload is None:
        s.sendall(f"{method} {path} HTTP/1.1\r\nHost: x\r\n"
                  f"Connection: close\r\n\r\n".encode())
    else:
        s.sendall(mk_req(payload, ctype))
    buf = bytearray()
    while True:
        chunk = s.recv(65536)
        if not chunk:
            break
        buf += chunk
        out = []
        if _count_responses(buf, out.append):
            s.close()
            return out[0]
    s.close()
    raise SystemExit(f"no complete response for {method} {path}")


body = {"attrs": [9.0, 1.0, 1.0]}
json_payload = json.dumps(body).encode("utf-8")
qps = {}
for front, wire in (("threaded", "json"), ("evloop", "packed")):
    os.environ["PIO_TPU_HTTP_FRONT"] = front
    server, svc = create_query_server(
        variant, host="127.0.0.1", port=0, ctx=ctx
    )
    server.start()
    try:
        req = mk_req(json_payload, "application/json") if wire == "json" \
            else mk_req(svc.pack_query_body(body), PACKED_QUERY_CONTENT_TYPE)
        drive(server.port, req, 4, 64)  # settle: cold scheduling noise
        # best-of-2: a single window on a shared 1-core host is noisy
        qps[front] = max(drive(server.port, req, 16, 600) for _ in (0, 1))
        if front != "evloop":
            continue
        out_json = one(server.port, "POST", "/queries.json",
                       json_payload, "application/json")
        out_packed = one(server.port, "POST", "/queries.json",
                         svc.pack_query_body(body),
                         PACKED_QUERY_CONTENT_TYPE)
        assert json.loads(out_packed) == json.loads(out_json), (
            out_packed, out_json)
        assert json.loads(out_packed).get("label") == "basic", out_packed
        metrics = one(server.port, "GET", "/metrics").decode("utf-8")
        fast = sum(
            float(line.rsplit(" ", 1)[1])
            for line in metrics.splitlines()
            if line.startswith("pio_tpu_http_parse_fastpath_total"))
        assert fast >= 600, (
            f"packed load did not take the parse fast path (sum={fast})")
        hp = json.loads(one(server.port, "GET", "/debug/hotpath.json"))
        frac = hp.get("attributedFraction")
        assert hp["requestCount"] >= 600, hp["requestCount"]
        assert frac is not None and frac >= 0.95, (
            f"evloop attribution {frac} < 0.95 over "
            f"{hp['requestCount']} requests "
            f"(residual {hp.get('residualMsPerRequest')} ms/req)")
    finally:
        server.stop()

# 5% scheduler-noise floor: best-of-2 windows on a shared host still
# land within a few percent of each other run to run, and a genuine
# evloop regression shows up far past that
assert qps["evloop"] >= 0.95 * qps["threaded"], (
    f"evloop front (packed wire) lost to the threaded baseline: "
    f"{qps['evloop']:.0f} vs {qps['threaded']:.0f} qps")
print(f"evfront stage: threaded-json={qps['threaded']:.0f}qps "
      f"evloop-packed={qps['evloop']:.0f}qps "
      f"speedup={qps['evloop'] / qps['threaded']:.2f}x")
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$EVFRONT_STAGE" \
    || fail "evloop front stage (qps/attribution/packed-parity assertions)"
echo "ok   evloop front: qps holds threaded baseline, attribution >= 95%, packed fastpath parity"

# --------------------------------------------- mesh-sharded serving
# ISSUE 10: the shard.* failpoints must be dump-visible, then a
# recommendation server on a simulated 8-device mesh with
# PIO_TPU_MESH_SERVE=1 (and sharded persistence on) must report a
# populated /stats.json "sharding" block, answer a steady window with
# the retrace counter flat, and agree with the host-scored reference.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"shard.place", "shard.reshard"}
missing = need - inv
assert not missing, f"shard failpoints missing from inventory: {missing}"
' || fail "shard.place/shard.reshard failpoints missing from --dump-failpoints"
echo "ok   shard.place/shard.reshard failpoints in lint inventory"

python - <<'PY' || fail "mesh-sharded stage (sharding block/retrace/parity assertions)"
"""Smoke stage: mesh-sharded serving via the partition-rule registry.

Trains ALS with sharded persistence on, serves it over a simulated
8-device CPU mesh with PIO_TPU_MESH_SERVE=1, and asserts from the
outside: the /stats.json sharding block names the mesh and the placed
model, rankings match the host-scored reference exactly, and the bucket
retrace counter stays flat across the steady-state window.
"""
import datetime as dt
import json
import os
import urllib.request

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
os.environ["PIO_TPU_SHARDED_PERSIST"] = "1"
os.environ["PIO_TPU_MESH_SERVE"] = "1"
os.environ["PIO_TPU_BUCKET_WARMUP"] = "1"
os.environ["PIO_TPU_BATCH_BUCKETS"] = "1,2,4"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.storage import App, Storage
from pio_tpu.templates.recommendation import Query
from pio_tpu.workflow import (
    build_engine, load_models_for_instance, run_train, variant_from_dict,
)

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-shard"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(12):
    for i in range(8):
        in_block = (u < 6) == (i < 4)
        le.insert(
            Event("rate", "user", f"u{u}", "item", f"i{i}",
                  properties={"rating": 5.0 if in_block else 1.0},
                  event_time=t0 + dt.timedelta(minutes=u * 60 + i)),
            app_id,
        )
variant = variant_from_dict({
    "id": "smoke-sharded",
    "engineFactory": "templates.recommendation",
    "datasource": {"params": {"app_name": "smoke-shard"}},
    "algorithms": [{"name": "als", "params": {
        "rank": 6, "num_iterations": 8, "lambda_": 0.05, "seed": 1}}],
})
engine, ep = build_engine(variant)
ctx = ComputeContext.create(seed=0)
n_dev = ctx.num_devices
assert n_dev == 8, f"expected the simulated 8-device mesh, got {n_dev}"
iid = run_train(engine, ep, variant, ctx=ctx)

# the sharded-persist artifacts must actually exist (blob is stripped)
ms = Storage.get_model_data_models()
assert ms.get(iid + ".shards") is not None, "shard manifest missing"

# headline constraint: a per-device budget the WHOLE model does not fit
# in (480 B of factors, 64 B/chip budget) — serving must only be
# possible sharded over the mesh
from pio_tpu.ops.topn import DeviceTopNScorer
from pio_tpu.parallel.partition import DeviceBudgetExceeded

os.environ["PIO_TPU_DEVICE_BUDGET_BYTES"] = "64"
probe = load_models_for_instance(iid, engine, ep, ctx)[0]
rows, cols = probe.factors.user_factors, probe.factors.item_factors
assert rows.nbytes + cols.nbytes > 64, "model unexpectedly fits one chip"
try:
    DeviceTopNScorer(rows, cols, prefer_device=True)
except DeviceBudgetExceeded:
    pass
else:
    raise AssertionError("single-chip placement ignored the budget")

# host-scored reference: the same instance through the direct predict
# path on host numpy — pin host mode so warmup never attempts a
# single-chip placement (the 64 B budget is still in force)
models = load_models_for_instance(iid, engine, ep, ctx)
serving = engine.make_serving(ep)
os.environ["PIO_TPU_SERVE_DEVICE"] = "host"
pairs = engine.algorithms_with_models(ep, models)
os.environ.pop("PIO_TPU_SERVE_DEVICE", None)
def host_ref(user, num):
    q = Query(user=user, num=num)
    preds = [algo.predict(m, q) for algo, m in pairs]
    return [s.item for s in serving.serve(q, preds).item_scores]

server, _service = create_query_server(
    variant, host="127.0.0.1", port=0, ctx=ctx
)
server.start()
try:
    base = f"http://127.0.0.1:{server.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/queries.json",
            data=json.dumps(body).encode("utf-8"),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode("utf-8"))

    def get(path):
        with urllib.request.urlopen(base + path, timeout=10) as r:
            return r.read().decode("utf-8")

    def counter(text, name):
        total = 0.0
        for line in text.splitlines():
            if line.startswith(name + "{") or line.startswith(name + " "):
                total += float(line.rsplit(" ", 1)[1])
        return total

    sh = json.loads(get("/stats.json"))["sharding"]
    assert sh["enabled"] and sh["meshDevices"] == 8, sh
    assert sh["models"] and sh["models"][0]["nDevices"] == 8, sh
    placed = counter(get("/metrics"), "pio_tpu_shard_bytes_placed_total")
    assert placed == sh["models"][0]["totalBytes"], (placed, sh)

    got = post({"user": "u0", "num": 4})  # warm route
    assert [s["item"] for s in got["itemScores"]] == host_ref("u0", 4), got
    m0 = get("/metrics")
    retr0 = counter(m0, "pio_tpu_bucket_retrace_total")
    N = 40
    for q in range(N):
        user = f"u{q % 12}"
        got = post({"user": user, "num": 4})
        assert [s["item"] for s in got["itemScores"]] == host_ref(user, 4), (
            user, got)
    retr = counter(get("/metrics"), "pio_tpu_bucket_retrace_total") - retr0
    assert retr == 0, f"bucket retraces moved by {retr} in steady state"
    print(f"sharded stage: mesh={sh['models'][0]['meshShape']} "
          f"placed={int(placed)}B retraces={int(retr)} parity exact over "
          f"{N} requests")
finally:
    server.stop()
PY
echo "ok   mesh-sharded serving: sharding block populated, retraces flat, host parity"

# --------------------------------------------- streamed sharded training
# ISSUE 14: the stream.* failpoints must be dump-visible, then a
# two-tower engine whose params exceed a tiny per-chip budget must (a)
# refuse single-chip placement, (b) train mesh-sharded with the epoch
# STREAMING through parallel/stream.py (the h2d counter moves), (c)
# persist sharded, and (d) deploy on the mesh answering at exact parity
# with the host-scored reference.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"stream.encode", "stream.put", "stream.dispatch"}
missing = need - inv
assert not missing, f"stream failpoints missing from inventory: {missing}"
' || fail "stream.* failpoints missing from --dump-failpoints"
echo "ok   stream.encode/put/dispatch failpoints in lint inventory"

python - <<'PY' || fail "streamed-training stage (budget/stream/persist/parity assertions)"
"""Smoke stage: streamed sharded training end to end.

Budget arithmetic at this scale: the two-tower params are 1792 B
unsharded, ~930 B/device sharded over model=2, and the staged epoch id
arrays are 768 B — so a 1200 B/chip budget rejects single-chip
placement, fits the sharded tables, and forces the auto feed to stream
batch spans (params + staged epoch would be ~1700 B).
"""
import datetime as dt
import json
import os
import urllib.request

os.environ["XLA_FLAGS"] = (
    os.environ.get("XLA_FLAGS", "")
    + " --xla_force_host_platform_device_count=8"
).strip()
os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_SOURCES_MEM_TYPE"] = "memory"
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "MEM"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "MEM"
os.environ["PIO_TPU_SHARDED_PERSIST"] = "1"
os.environ["PIO_TPU_MESH_SERVE"] = "1"

import numpy as np

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.server import create_query_server
from pio_tpu.storage import App, Storage
from pio_tpu.templates.recommendation import Query
from pio_tpu.workflow import (
    build_engine, load_models_for_instance, run_train, variant_from_dict,
)

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-stream"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(12):
    for i in range(8):
        in_block = (u < 6) == (i < 4)
        le.insert(
            Event("rate", "user", f"u{u}", "item", f"i{i}",
                  properties={"rating": 5.0 if in_block else 1.0},
                  event_time=t0 + dt.timedelta(minutes=u * 60 + i)),
            app_id,
        )
variant = variant_from_dict({
    "id": "smoke-streamed",
    "engineFactory": "templates.twotower",
    "datasource": {"params": {"app_name": "smoke-stream"}},
    "algorithms": [{"name": "twotower", "params": {
        "embed_dim": 8, "hidden": 8, "out_dim": 8, "steps": 30,
        "batch_size": 16, "model_parallel": 2, "seed": 1}}],
})
engine, ep = build_engine(variant)
ctx = ComputeContext.create(seed=0)
assert ctx.num_devices == 8, f"expected 8 simulated devices, got {ctx.num_devices}"

os.environ["PIO_TPU_DEVICE_BUDGET_BYTES"] = "1200"

# (a) single-chip placement must refuse the budget
from pio_tpu.models.two_tower import TwoTowerConfig, train_two_tower
from pio_tpu.parallel.partition import DeviceBudgetExceeded

rng = np.random.default_rng(0)
cfg = TwoTowerConfig(embed_dim=8, hidden=8, out_dim=8, steps=30,
                     batch_size=16, seed=1)
try:
    train_two_tower(None, rng.integers(0, 12, 96).astype(np.int32),
                    rng.integers(0, 8, 96).astype(np.int32), 12, 8, cfg)
except DeviceBudgetExceeded:
    pass
else:
    raise AssertionError("single-chip placement ignored the budget")

# (b) mesh training streams: the feed's h2d counter must move
from pio_tpu.parallel.stream import _H2D_BYTES

h2d0 = _H2D_BYTES.value()
iid = run_train(engine, ep, variant, ctx=ctx)
h2d = _H2D_BYTES.value() - h2d0
assert h2d > 0, "training under budget did not stream (h2d counter flat)"

# (c) sharded persist artifacts exist (blob is shard-stripped)
ms = Storage.get_model_data_models()
assert ms.get(iid + ".shards") is not None, "shard manifest missing"

# (d) mesh deploy answers at exact parity with the host reference
models = load_models_for_instance(iid, engine, ep, ctx)
serving = engine.make_serving(ep)
os.environ["PIO_TPU_SERVE_DEVICE"] = "host"
pairs = engine.algorithms_with_models(ep, models)
os.environ.pop("PIO_TPU_SERVE_DEVICE", None)

def host_ref(user, num):
    q = Query(user=user, num=num)
    preds = [algo.predict(m, q) for algo, m in pairs]
    return [s.item for s in serving.serve(q, preds).item_scores]

server, _service = create_query_server(
    variant, host="127.0.0.1", port=0, ctx=ctx
)
server.start()
try:
    base = f"http://127.0.0.1:{server.port}"

    def post(body):
        req = urllib.request.Request(
            base + "/queries.json",
            data=json.dumps(body).encode("utf-8"),
            headers={"Content-Type": "application/json"},
        )
        with urllib.request.urlopen(req, timeout=30) as r:
            return json.loads(r.read().decode("utf-8"))

    for q in range(24):
        user = f"u{q % 12}"
        got = post({"user": user, "num": 4})
        assert [s["item"] for s in got["itemScores"]] == host_ref(user, 4), (
            user, got)
    print(f"streamed stage: h2d={int(h2d)}B streamed through the feed, "
          f"sharded persist + mesh deploy, parity exact over 24 requests")
finally:
    server.stop()
PY
echo "ok   streamed sharded training: budget refusal, streamed feed, sharded persist, serve parity"

# -------------------------------------------------- fleet federation
# ISSUE 11: the fleet telemetry plane. Three live members — a
# replicated-partlog event leader (subprocess), its follower's status
# sidecar, and a dashboard — federate into one fleetd whose
# /fleet.json must report them all up with non-null replication lag;
# killing the follower must flip it to down within two scrape
# intervals while the federated counters keep the last-seen snapshot
# in the sums.
FLEET_STAGE="$WORKDIR/fleet_stage.py"
cat > "$FLEET_STAGE" <<'PY'
"""Smoke stage: cross-host metric federation + cluster status."""
import json
import os
import signal
import subprocess
import sys
import time
import urllib.error
import urllib.request

WORKDIR = sys.argv[1]

from pio_tpu.server.dashboard import create_dashboard
from pio_tpu.server.fleetd import (
    create_fleet_server, create_follower_status_server,
)
from pio_tpu.storage.partlog.replication import FollowerServer

froot = os.path.join(WORKDIR, "fleet-follower")
follower = FollowerServer(froot)

leader_root = os.path.join(WORKDIR, "fleet-leader")
port_file = os.path.join(WORKDIR, "fleet-port")
info_file = os.path.join(WORKDIR, "fleet-info")

LEADER_SRC = r'''
import json, os, signal, sys
from pio_tpu.server import create_event_server
from pio_tpu.storage import AccessKey, App, Storage

app_id = Storage.get_meta_data_apps().insert(App(0, "fleet"))
key = Storage.get_meta_data_access_keys().insert(AccessKey("", app_id))
server = create_event_server(host="127.0.0.1", port=0).start()
info_file, port_file = sys.argv[1], sys.argv[2]
with open(info_file, "w") as f:
    json.dump({"key": key}, f)
with open(port_file + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(port_file + ".tmp", port_file)
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
'''

env = dict(os.environ)
env.update({
    "PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE": "PL",
    "PIO_STORAGE_SOURCES_PL_TYPE": "partlog",
    "PIO_STORAGE_SOURCES_PL_PATH": leader_root,
    "PIO_STORAGE_REPOSITORIES_METADATA_SOURCE": "MEM",
    "PIO_STORAGE_SOURCES_MEM_TYPE": "memory",
    "PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE": "MEM",
    "PIO_TPU_PARTLOG_PARTITIONS": "2",
    "PIO_TPU_PARTLOG_REPLICAS": f"127.0.0.1:{follower.port}",
    # batch durability: the follower mirrors asynchronously, so the
    # leader keeps acking (and counters keep summing) after we kill it
    "PIO_TPU_DURABILITY": "batch",
})
proc = subprocess.Popen(
    [sys.executable, "-c", LEADER_SRC, info_file, port_file], env=env)

servers = []


def cleanup():
    for s in servers:
        try:
            s.stop()
        except Exception:
            pass
    if proc.poll() is None:
        proc.kill()
        proc.wait()
    try:
        follower.stop()
    except Exception:
        pass


try:
    deadline = time.time() + 60
    while not os.path.exists(port_file):
        if proc.poll() is not None:
            raise SystemExit("event leader died during boot")
        if time.time() > deadline:
            raise SystemExit("event leader never published its port")
        time.sleep(0.2)
    with open(port_file) as f:
        leader = "127.0.0.1:" + f.read().strip()
    with open(info_file) as f:
        key = json.load(f)["key"]

    sidecar = create_follower_status_server(
        follower, host="127.0.0.1", port=0).start()
    servers.append(sidecar)
    dash = create_dashboard(host="127.0.0.1", port=0)
    dash.start()
    servers.append(dash)

    def post(n):
        for i in range(n):
            body = json.dumps({
                "event": "fleet", "entityType": "user",
                "entityId": f"u{i}", "properties": {"seq": i},
                "eventTime": "2026-03-01T10:00:00Z",
            }).encode("utf-8")
            req = urllib.request.Request(
                f"http://{leader}/events.json?accessKey=" + key,
                data=body, headers={"Content-Type": "application/json"})
            with urllib.request.urlopen(req, timeout=15) as r:
                assert r.status == 201, r.status

    post(8)
    # async replication: wait until the follower acked every committed
    # byte so the lag the fleet reports is concrete (and zero)
    deadline = time.time() + 30
    while time.time() < deadline:
        with urllib.request.urlopen(
                f"http://{leader}/storage.json", timeout=10) as r:
            topo = json.loads(r.read().decode("utf-8"))
        committed = {str(p["partition"]): p["committed_bytes"]
                     for p in topo["partition_detail"]}
        acked = (topo["replication"] or {}).get("min_acked") or {}
        if sum(committed.values()) > 0 and all(
                acked.get(k) == v for k, v in committed.items()):
            break
        time.sleep(0.1)
    else:
        raise SystemExit(f"follower never caught up: {topo}")

    members = ",".join([
        leader,
        f"127.0.0.1:{sidecar.port}",
        f"127.0.0.1:{dash.port}",
    ])
    fleetd = create_fleet_server(members, host="127.0.0.1", port=0,
                                 interval_s=0.3)
    fleetd.start()
    servers.append(fleetd)
    agg = fleetd.service.agg
    furl = f"http://127.0.0.1:{fleetd.port}"

    def get(url, path):
        with urllib.request.urlopen(url + path, timeout=10) as r:
            return r.status, r.read().decode("utf-8")

    # readiness gates on the first full scrape pass
    try:
        status, _ = get(furl, "/readyz")
    except urllib.error.HTTPError as e:
        status = e.code
    assert status == 503, f"fleetd ready before any scrape ({status})"
    agg.start()
    deadline = time.time() + 30
    while agg.passes < 1 and time.time() < deadline:
        time.sleep(0.05)
    assert get(furl, "/readyz")[0] == 200, "fleetd never became ready"

    pay = json.loads(get(furl, "/fleet.json")[1])
    assert pay["fleet"]["members"] == 3, pay["fleet"]
    assert pay["fleet"]["up"] == 3, pay["fleet"]
    roles = {m["member"]: m["role"] for m in pay["members"]}
    assert roles[leader] == "leader", roles
    assert roles[f"127.0.0.1:{sidecar.port}"] == "follower", roles

    # replication lag is concrete numbers, not nulls
    lead = pay["partlog"]["leaders"][0]
    assert len(lead["partitionDetail"]) == 2, lead
    total_committed = 0
    for p in lead["partitionDetail"]:
        total_committed += p["committedBytes"]
        fol = p["followers"][0]
        assert fol["ackedBytes"] is not None, p
        assert fol["lagBytes"] is not None, p
    assert total_committed > 0, lead

    # federated /metrics: every member's families, member-labeled, and
    # counter sums matching the leader's own scrape
    fed = get(furl, "/metrics")[1]
    for needle in (
        f'pio_tpu_events_ingested_total{{', f'pio_tpu_member="{leader}"',
        f'pio_tpu_repl_follower_position_bytes{{partition="0",'
        f'pio_tpu_member="127.0.0.1:{sidecar.port}"}}',
        f'pio_tpu_fleet_member_up{{member="{leader}"}} 1',
    ):
        assert needle in fed, f"federated scrape missing {needle!r}"
    own = get(f"http://{leader}", "/metrics")[1]
    own_ingested = sum(
        float(line.rsplit(" ", 1)[1])
        for line in own.splitlines()
        if line.startswith("pio_tpu_events_ingested_total{"))
    fed_ingested = sum(
        float(line.rsplit(" ", 1)[1])
        for line in fed.splitlines()
        if line.startswith("pio_tpu_events_ingested_total{")
        and f'pio_tpu_member="{leader}"' in line)
    assert fed_ingested == own_ingested >= 8, (fed_ingested, own_ingested)

    # SIGKILL the follower's surfaces: down within two scrape
    # intervals, last-seen snapshot retained in the federation
    agg.stale_after_s = 0.3
    agg.down_after_s = 0.6  # = two scrape intervals
    sidecar.stop()
    servers.remove(sidecar)
    follower.stop()
    # poll for BOTH: the dead follower marked down AND the live leader
    # seen up in the same payload (with stale_after == interval the
    # leader legitimately reads "stale" between scrapes, so a
    # single-instant assert on its status races the scrape loop)
    deadline = time.time() + 30
    while time.time() < deadline:
        pay = json.loads(get(furl, "/fleet.json")[1])
        by = {m["member"]: m["status"] for m in pay["members"]}
        if by[f"127.0.0.1:{sidecar.port}"] == "down" \
                and by[leader] == "up":
            break
        time.sleep(0.1)
    else:
        raise SystemExit(
            f"follower never down with leader up in one payload: {by}")

    post(4)  # live members keep counting while one is dark
    time.sleep(1.0)  # > one scrape interval
    fed2 = get(furl, "/metrics")[1]
    assert (f'pio_tpu_fleet_member_up'
            f'{{member="127.0.0.1:{sidecar.port}"}} 0') in fed2, "up!=0"
    assert (f'pio_tpu_repl_follower_position_bytes{{partition="0",'
            f'pio_tpu_member="127.0.0.1:{sidecar.port}"}}') in fed2, (
        "dead member's snapshot vanished from the federation")
    fed2_ingested = sum(
        float(line.rsplit(" ", 1)[1])
        for line in fed2.splitlines()
        if line.startswith("pio_tpu_events_ingested_total{")
        and f'pio_tpu_member="{leader}"' in line)
    assert fed2_ingested >= own_ingested + 4, (fed2_ingested, own_ingested)

    print(f"fleet stage: 3 members federated, "
          f"committed={int(total_committed)}B lag reported, follower "
          f"down in <2 intervals, sums {int(fed_ingested)} -> "
          f"{int(fed2_ingested)} with snapshot retained")
finally:
    cleanup()
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$FLEET_STAGE" "$WORKDIR" \
    || fail "fleet federation stage (liveness/lag/federated-sum assertions)"
echo "ok   fleet federation: 3 members, lag reported, follower death detected, sums retained"

# --------------------------------------------- training telemetry plane
# ISSUE 16: live /train.json progress from REAL `pio train` CLI runs —
# monotonically advancing step/epoch and a non-empty loss window while
# the run is in flight; a fleetd that shows the trainer member up
# during the run and down after its exit; and the run ledger, where a
# second run slowed by an injected feed-latency failpoint must be
# flagged by `pio runs --diff`.
TRAIN_STAGE="$WORKDIR/train_stage.py"
cat > "$TRAIN_STAGE" <<'PY'
"""Smoke stage: training telemetry plane end to end."""
import json
import os
import re
import subprocess
import sys
import threading
import time
import urllib.error
import urllib.request

WORKDIR = sys.argv[1]

# sqlite storage shared between the seeding parent and the CLI children
os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_SOURCES_SQ_TYPE"] = "sqlite"
os.environ["PIO_STORAGE_SOURCES_SQ_PATH"] = os.path.join(
    WORKDIR, "train_stage.db")
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "SQ"
# small stream chunks: many feed puts -> many failpoint hits, and the
# step counter advances chunk by chunk while we poll
os.environ["PIO_TPU_TRAIN_STREAM_MB"] = "0.02"

import datetime as dt

from pio_tpu.data import Event
from pio_tpu.storage import App, Storage

app_id = Storage.get_meta_data_apps().insert(App(0, "twsmoke"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(24):
    for i in range(12):
        if (u < 12) == (i < 6):
            le.insert(Event("rate", "user", f"u{u}", "item", f"i{i}",
                            properties={"rating": 5.0}, event_time=t0),
                      app_id)

engine_json = os.path.join(WORKDIR, "twsmoke-engine.json")
with open(engine_json, "w") as f:
    json.dump({
        "id": "twsmoke",
        "engineFactory": "templates.twotower",
        "datasource": {"params": {"app_name": "twsmoke"}},
        "algorithms": [{"name": "twotower", "params": {
            "embed_dim": 8, "hidden": 16, "out_dim": 8,
            "steps": 120, "batch_size": 256, "stream": "on"}}],
    }, f)


def run_train(faults, watch=False):
    """One `pio train` CLI run; with watch, poll /train.json live and
    track the trainer member through a fleetd."""
    proc = subprocess.Popen(
        [sys.executable, "-u", "-m", "pio_tpu", "train",
         "--engine-json", engine_json, "--status-port", "0",
         "--faults", faults],
        stdout=subprocess.PIPE, stderr=subprocess.STDOUT, text=True,
        env=dict(os.environ),
    )
    port = None
    deadline = time.time() + 120
    lines = []
    while time.time() < deadline:
        line = proc.stdout.readline()
        if not line:
            break
        lines.append(line)
        m = re.search(r"status sidecar on 127\.0\.0\.1:(\d+)", line)
        if m:
            port = int(m.group(1))
            break
    assert port, f"sidecar port never printed: {''.join(lines)}"
    # drain the rest of stdout so the child never blocks on the pipe
    t = threading.Thread(
        target=lambda: lines.extend(iter(proc.stdout.readline, "")),
        daemon=True)
    t.start()
    samples = []
    fleetd = None
    try:
        if watch:
            from pio_tpu.server.fleetd import create_fleet_server

            fleetd = create_fleet_server(
                f"127.0.0.1:{port}", host="127.0.0.1", port=0,
                interval_s=0.2)
            fleetd.start()
            fleetd.service.agg.start()
        seen_up = False
        while proc.poll() is None:
            try:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{port}/train.json",
                        timeout=5) as r:
                    samples.append(json.loads(r.read().decode("utf-8")))
            except (urllib.error.URLError, OSError):
                pass  # before the run activates / after it ends
            if (watch and not seen_up and samples
                    and samples[-1].get("step", 0) > 0):
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{fleetd.port}/fleet.json",
                        timeout=5) as r:
                    fp = json.loads(r.read().decode("utf-8"))
                me = fp["members"][0]
                if me["role"] == "trainer" and me["status"] == "up":
                    assert me["training"]["runId"], me
                    seen_up = True
            time.sleep(0.02)
        proc.wait(timeout=120)
        assert proc.returncode == 0, (
            f"pio train failed ({proc.returncode}): {''.join(lines)}")
        if watch:
            assert seen_up, "fleetd never saw the trainer member up"
            # the sidecar died with its run: down within a few scrapes
            agg = fleetd.service.agg
            agg.stale_after_s = 0.2
            agg.down_after_s = 0.4
            deadline = time.time() + 30
            while time.time() < deadline:
                with urllib.request.urlopen(
                        f"http://127.0.0.1:{fleetd.port}/fleet.json",
                        timeout=5) as r:
                    fp = json.loads(r.read().decode("utf-8"))
                if fp["members"][0]["status"] == "down":
                    break
                time.sleep(0.1)
            else:
                raise SystemExit(
                    f"trainer member never marked down: {fp['members']}")
            assert fp["members"][0]["role"] == "trainer", fp["members"]
    finally:
        if proc.poll() is None:
            proc.kill()
        if fleetd is not None:
            fleetd.service.agg.stop()
            fleetd.stop()
    return samples


samples = run_train("stream.put=latency:30ms", watch=True)
steps = [s["step"] for s in samples]
assert steps, "no /train.json samples during the run"
assert steps == sorted(steps), f"step went backwards: {steps}"
assert max(steps) > 0, f"step never advanced: {steps}"
assert len(set(s for s in steps if s > 0)) >= 2, (
    f"step did not advance chunk by chunk: {steps}")
epochs = [s["epoch"] for s in samples if s["epoch"] is not None]
assert epochs == sorted(epochs), f"epoch went backwards: {epochs}"
with_loss = [s for s in samples if s["step"] > 0]
assert with_loss and with_loss[-1]["lossWindow"], (
    "loss window empty while steps advanced")
assert any(s["stream"]["streamed"] for s in with_loss), "feed not streamed"

# run 2: same engine, feed slowed 10x by the injected failpoint
run_train("stream.put=latency:300ms")

diff = subprocess.run(
    [sys.executable, "-m", "pio_tpu", "runs",
     "--engine-json", engine_json, "--diff"],
    capture_output=True, text=True, env=dict(os.environ), timeout=120,
)
assert diff.returncode == 1, (
    f"pio runs --diff did not flag the slowed run:\n{diff.stdout}\n"
    f"{diff.stderr}")
assert "REGRESSION" in diff.stdout, diff.stdout
assert "train_seconds" in diff.stderr, diff.stderr

listing = subprocess.run(
    [sys.executable, "-m", "pio_tpu", "runs",
     "--engine-json", engine_json],
    capture_output=True, text=True, env=dict(os.environ), timeout=120,
)
assert listing.returncode == 0, listing.stderr
assert listing.stdout.count("COMPLETED") == 2, listing.stdout

n_steps = [s for s in steps if s > 0]
print(f"train stage: {len(samples)} live polls, step walked "
      f"{n_steps[0]} -> {n_steps[-1]}/120 monotonically, loss window "
      f"{len(with_loss[-1]['lossWindow'])} entries, trainer member "
      f"up->down in fleetd, `pio runs --diff` flagged the slowed run")
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$TRAIN_STAGE" "$WORKDIR" \
    || fail "training telemetry stage (progress/ledger/fleet assertions)"
echo "ok   training telemetry: live /train.json progress, fleetd trainer tracking, runs-ledger regression flagged"

# ------------------------------------------------ serving fabric router
# ISSUE 18: the router failpoints must be dump-visible, then the chaos
# drill — two REAL serving members over shared sqlite model storage
# with a routerd front tier fanning steady threaded load; SIGKILL
# member 1 mid-load. Every request must still be answered 200 (zero
# non-inflight 5xx: the router forces the dead member out of the ring
# on the first transport error and retries on member 2), /router.json
# must show the remap within two scrape intervals, and the
# pio_tpu_router_* families must account the traffic.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"router.pick", "router.forward", "router.verify"}
missing = need - inv
assert not missing, f"router failpoints missing from inventory: {missing}"
' || fail "router.pick/forward/verify failpoints missing from --dump-failpoints"
echo "ok   router failpoints in lint inventory"

ROUTER_STAGE="$WORKDIR/router_stage.py"
cat > "$ROUTER_STAGE" <<'PY'
"""Smoke stage: serving-fabric failover under SIGKILL.

Trains the tiny recommendation engine once into sqlite, boots TWO real
query-server subprocesses over that shared model store, fronts them
with an in-process routerd (fast 0.3 s scrape), then drives steady
threaded load through the router while member 1 is SIGKILLed
mid-flight. The bar, same as the partlog drill: zero non-inflight 5xx
— the router's one-shot retry plus passive forced-down must absorb the
kill invisibly — and the outside view (/router.json, /metrics) must
show member 1 leaving the ring and member 2 absorbing its keyspace.
"""
import datetime as dt
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

WORKDIR = sys.argv[1]

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_SOURCES_SQ_TYPE"] = "sqlite"
os.environ["PIO_STORAGE_SOURCES_SQ_PATH"] = os.path.join(
    WORKDIR, "router.db")
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "SQ"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

VARIANT = {
    "id": "smoke-router-rec",
    "engineFactory": "templates.recommendation",
    "datasource": {"params": {"app_name": "smoke-router"}},
    "algorithms": [{"name": "als", "params": {
        "rank": 4, "num_iterations": 4, "lambda_": 0.1}}],
}

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-router"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(8):
    for i in range(6):
        in_block = (u < 4) == (i < 3)
        le.insert(
            Event("rate", "user", f"u{u}", "item", f"i{i}",
                  properties={"rating": 5.0 if in_block else 1.0},
                  event_time=t0),
            app_id,
        )
variant = variant_from_dict(VARIANT)
engine, ep = build_engine(variant)
run_train(engine, ep, variant, ctx=ComputeContext.local())

variant_file = os.path.join(WORKDIR, "router-variant.json")
with open(variant_file, "w") as f:
    json.dump(VARIANT, f)

MEMBER_SRC = r'''
import json, os, signal, sys
from pio_tpu.server import create_query_server
from pio_tpu.workflow import variant_from_dict

with open(sys.argv[1]) as f:
    variant = variant_from_dict(json.load(f))
server, _service = create_query_server(variant, host="127.0.0.1", port=0)
server.start()
with open(sys.argv[2] + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(sys.argv[2] + ".tmp", sys.argv[2])  # atomic publish
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
'''

port_files = [os.path.join(WORKDIR, f"router-m{i}-port") for i in (1, 2)]
members = [
    subprocess.Popen(
        [sys.executable, "-c", MEMBER_SRC, variant_file, pf],
        env=dict(os.environ))
    for pf in port_files
]
router_server = None
stop_load = threading.Event()


def _cleanup():
    stop_load.set()
    for p in members:
        if p.poll() is None:
            p.kill()
            p.wait()
    if router_server is not None:
        router_server.service.stop()
        router_server.stop()


def _wait_ready(base, deadline):
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
                if r.status == 200:
                    return
        except Exception:
            pass
        time.sleep(0.2)
    raise SystemExit(f"{base} never became ready")


try:
    deadline = time.time() + 120
    ports = []
    for pf, p in zip(port_files, members):
        while not os.path.exists(pf):
            if p.poll() is not None:
                raise SystemExit("serving member died during boot")
            if time.time() > deadline:
                raise SystemExit("serving member never published its port")
            time.sleep(0.2)
        with open(pf) as f:
            ports.append(int(f.read().strip()))
    for port in ports:
        _wait_ready(f"http://127.0.0.1:{port}", deadline)

    from pio_tpu.server.routerd import create_router_server

    targets = [
        (f"m{i + 1}", f"http://127.0.0.1:{port}")
        for i, port in enumerate(ports)
    ]
    router_server = create_router_server(
        targets, host="127.0.0.1", port=0, partitions=2, interval_s=0.3,
    ).start()
    router_server.service.start()
    rbase = f"http://127.0.0.1:{router_server.port}"
    _wait_ready(rbase, time.time() + 30)

    statuses = []
    lock = threading.Lock()

    def load(t):
        i = 0
        while not stop_load.is_set():
            i += 1
            body = json.dumps(
                {"user": f"u{(t * 31 + i) % 8}", "num": 3}
            ).encode("utf-8")
            req = urllib.request.Request(
                rbase + "/queries.json", data=body,
                headers={"Content-Type": "application/json"})
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    ok = r.status == 200 and b"itemScores" in r.read()
                    code = r.status if ok else -1
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception as e:
                code = f"{type(e).__name__}"
            with lock:
                statuses.append(code)

    threads = [
        threading.Thread(target=load, args=(t,), daemon=True)
        for t in range(4)
    ]
    for t in threads:
        t.start()

    deadline = time.time() + 60
    while True:
        with lock:
            n = len(statuses)
        if n >= 20:
            break
        if time.time() > deadline:
            raise SystemExit(f"only {n} routed requests in 60s")
        time.sleep(0.05)

    # mid-load SIGKILL: member 1 vanishes with its keyspace
    os.kill(members[0].pid, signal.SIGKILL)
    members[0].wait()
    killed_at = time.time()
    time.sleep(2.0)  # keep the load running across the failover
    stop_load.set()
    for t in threads:
        t.join(timeout=30)

    bad = [s for s in statuses if s != 200]
    assert not bad, (
        f"{len(bad)}/{len(statuses)} routed requests failed across the "
        f"SIGKILL: {bad[:5]} (want zero non-inflight 5xx)")

    # the ring must have remapped within ~2 scrape intervals; allow
    # generous wall-clock slack for the assertion poll itself
    snap = None
    deadline = killed_at + 15
    while time.time() < deadline:
        with urllib.request.urlopen(rbase + "/router.json", timeout=5) as r:
            snap = json.loads(r.read().decode("utf-8"))
        if snap["ring"]["routable"] == ["m2"]:
            break
        time.sleep(0.1)
    else:
        raise SystemExit(f"m1 never left the ring: {snap['members']}")
    by_member = {m["member"]: m for m in snap["members"]}
    assert by_member["m1"]["errors"] >= 1, by_member["m1"]
    assert by_member["m2"]["forwarded"] >= 1, by_member["m2"]
    assert snap["ring"]["partitions"] == 2, snap["ring"]

    with urllib.request.urlopen(rbase + "/metrics", timeout=5) as r:
        metrics = r.read().decode("utf-8")
    for fam in ("pio_tpu_router_forwarded_total{",
                "pio_tpu_router_forward_errors_total{",
                "pio_tpu_router_member_routable{",
                "pio_tpu_router_pick_seconds_bucket{",
                "pio_tpu_router_ring_size 1"):
        assert fam in metrics, f"/metrics missing {fam}"

    print(f"router stage: {len(statuses)} routed requests, 0 failed "
          f"across SIGKILL of m1; m2 absorbed "
          f"{by_member['m2']['forwarded']} forwards "
          f"({by_member['m2']['retried']} retries)")
finally:
    _cleanup()
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$ROUTER_STAGE" "$WORKDIR" \
    || fail "serving fabric router stage (failover/ring/metrics assertions)"
echo "ok   serving fabric: member SIGKILLed mid-load, zero failed requests, ring remapped to the survivor"

# ------------------------------------------------ progressive rollout
# ISSUE 19: the rollout failpoints must be dump-visible, then the
# progressive-delivery chaos drill — a clean candidate must walk
# shadow -> canary -> promoted on its own (ring generation flipping
# exactly once per member, only on a verified 200, shadow mirroring
# adding no measurable incumbent p50), and a candidate SIGKILLed
# mid-canary must be auto-rolled-back by the judge with the incumbent
# restored byte-identically and zero interactive 5xx throughout.
python -m pio_tpu.tools.cli lint --dump-failpoints pio_tpu | python -c '
import json, sys
inv = {f["point"] for f in json.load(sys.stdin)["failpoints"]}
need = {"rollout.mirror", "rollout.judge", "rollout.promote",
        "rollout.rollback"}
missing = need - inv
assert not missing, f"rollout failpoints missing from inventory: {missing}"
' || fail "rollout.* failpoints missing from --dump-failpoints"
echo "ok   rollout failpoints in lint inventory"

ROLLOUT_STAGE="$WORKDIR/rollout_stage.py"
cat > "$ROLLOUT_STAGE" <<'PY'
"""Smoke stage: progressive delivery — auto-promote and auto-rollback.

Trains one incumbent and two candidate instances of the tiny
recommendation engine into shared sqlite (fixed training seed, so a
clean candidate answers byte-identically to the incumbent), boots two
incumbent members plus two candidate members as real query-server
subprocesses, fronts the incumbents with an in-process routerd, and
drives steady threaded load the whole time.  Drill one: POST /rollout
with a clean candidate and let the controller walk shadow -> canary ->
promoted unattended; the member generation must flip exactly once per
member and only on a verified 200, and the shadow window's client p50
must sit inside the pre-rollout noise floor (mirroring is off the
relay path).  Drill two: start a second rollout, SIGKILL the candidate
mid-canary; the judge must see the scrape go dark, auto-rollback,
leave the incumbent members untouched (same instance, same generation,
same manifest sha set), and no client request may fail in either
drill.
"""
import datetime as dt
import json
import os
import signal
import subprocess
import sys
import threading
import time
import urllib.request

WORKDIR = sys.argv[1]

os.environ["PIO_STORAGE_REPOSITORIES_EVENTDATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_SOURCES_SQ_TYPE"] = "sqlite"
os.environ["PIO_STORAGE_SOURCES_SQ_PATH"] = os.path.join(
    WORKDIR, "rollout.db")
os.environ["PIO_STORAGE_REPOSITORIES_METADATA_SOURCE"] = "SQ"
os.environ["PIO_STORAGE_REPOSITORIES_MODELDATA_SOURCE"] = "SQ"

import pio_tpu.templates  # noqa: F401  (registers the factory)
from pio_tpu.controller import ComputeContext
from pio_tpu.data import Event
from pio_tpu.storage import App, Storage
from pio_tpu.workflow import build_engine, run_train, variant_from_dict

VARIANT = {
    "id": "smoke-rollout-rec",
    "engineFactory": "templates.recommendation",
    "datasource": {"params": {"app_name": "smoke-rollout"}},
    "algorithms": [{"name": "als", "params": {
        "rank": 4, "num_iterations": 4, "lambda_": 0.1}}],
}

app_id = Storage.get_meta_data_apps().insert(App(0, "smoke-rollout"))
le = Storage.get_levents()
t0 = dt.datetime(2026, 3, 1, tzinfo=dt.timezone.utc)
for u in range(8):
    for i in range(6):
        in_block = (u < 4) == (i < 3)
        le.insert(
            Event("rate", "user", f"u{u}", "item", f"i{i}",
                  properties={"rating": 5.0 if in_block else 1.0},
                  event_time=t0),
            app_id,
        )
variant = variant_from_dict(VARIANT)
iids = []
for _ in range(3):
    engine, ep = build_engine(variant)
    iids.append(run_train(engine, ep, variant, ctx=ComputeContext.local()))
INC, CAND1, CAND2 = iids

variant_file = os.path.join(WORKDIR, "rollout-variant.json")
with open(variant_file, "w") as f:
    json.dump(VARIANT, f)

MEMBER_SRC = r'''
import json, os, signal, sys
from pio_tpu.server import create_query_server
from pio_tpu.workflow import variant_from_dict

with open(sys.argv[1]) as f:
    variant = variant_from_dict(json.load(f))
server, _service = create_query_server(
    variant, host="127.0.0.1", port=0, instance_id=sys.argv[3])
server.start()
with open(sys.argv[2] + ".tmp", "w") as f:
    f.write(str(server.port))
os.rename(sys.argv[2] + ".tmp", sys.argv[2])  # atomic publish
signal.sigwait({signal.SIGTERM, signal.SIGINT})
server.stop()
'''

# m1/m2 are the incumbent ring; c1/c2 boot on the incumbent instance
# and only ever serve a candidate through the verified deploy path
names = ("m1", "m2", "c1", "c2")
port_files = {n: os.path.join(WORKDIR, f"rollout-{n}-port") for n in names}
procs = {
    n: subprocess.Popen(
        [sys.executable, "-c", MEMBER_SRC, variant_file, port_files[n], INC],
        env=dict(os.environ))
    for n in names
}
router_server = None
stop_load = threading.Event()


def _cleanup():
    stop_load.set()
    for p in procs.values():
        if p.poll() is None:
            p.kill()
            p.wait()
    if router_server is not None:
        router_server.service.stop()
        router_server.stop()


def _wait_ready(base, deadline):
    while time.time() < deadline:
        try:
            with urllib.request.urlopen(base + "/readyz", timeout=5) as r:
                if r.status == 200:
                    return
        except Exception:
            pass
        time.sleep(0.2)
    raise SystemExit(f"{base} never became ready")


try:
    deadline = time.time() + 180
    ports = {}
    for n in names:
        pf, p = port_files[n], procs[n]
        while not os.path.exists(pf):
            if p.poll() is not None:
                raise SystemExit(f"member {n} died during boot")
            if time.time() > deadline:
                raise SystemExit(f"member {n} never published its port")
            time.sleep(0.2)
        with open(pf) as f:
            ports[n] = int(f.read().strip())
    for n in names:
        _wait_ready(f"http://127.0.0.1:{ports[n]}", deadline)

    from pio_tpu.server.routerd import create_router_server

    targets = [(n, f"http://127.0.0.1:{ports[n]}") for n in ("m1", "m2")]
    router_server = create_router_server(
        targets, host="127.0.0.1", port=0, partitions=2, interval_s=0.3,
    ).start()
    router_server.service.start()
    rbase = f"http://127.0.0.1:{router_server.port}"
    _wait_ready(rbase, time.time() + 30)

    records = []  # (done_at, elapsed_s, status)
    lock = threading.Lock()

    def load(t):
        i = 0
        while not stop_load.is_set():
            i += 1
            body = json.dumps(
                {"user": f"u{(t * 31 + i) % 8}", "num": 3}
            ).encode("utf-8")
            req = urllib.request.Request(
                rbase + "/queries.json", data=body,
                headers={"Content-Type": "application/json"})
            t1 = time.time()
            try:
                with urllib.request.urlopen(req, timeout=20) as r:
                    ok = r.status == 200 and b"itemScores" in r.read()
                    code = r.status if ok else -1
            except urllib.error.HTTPError as e:
                code = e.code
            except Exception as e:
                code = f"{type(e).__name__}"
            with lock:
                records.append((time.time(), time.time() - t1, code))

    threads = [
        threading.Thread(target=load, args=(t,), daemon=True)
        for t in range(3)
    ]
    for t in threads:
        t.start()

    def rollout_json():
        with urllib.request.urlopen(rbase + "/rollout.json", timeout=5) as r:
            return json.loads(r.read().decode("utf-8"))

    def deploy_report(name):
        url = f"http://127.0.0.1:{ports[name]}/deploy.json"
        with urllib.request.urlopen(url, timeout=5) as r:
            return json.loads(r.read().decode("utf-8"))

    def post_rollout(payload):
        req = urllib.request.Request(
            rbase + "/rollout", data=json.dumps(payload).encode("utf-8"),
            headers={"Content-Type": "application/json"})
        with urllib.request.urlopen(req, timeout=30) as r:
            assert r.status == 202, f"POST /rollout answered {r.status}"

    def wait_stage(want, timeout_s):
        deadline = time.time() + timeout_s
        snap = None
        while time.time() < deadline:
            snap = rollout_json()
            if snap["stage"] == want:
                return snap
            if snap["stage"] in ("failed", "rolled_back") \
                    and want not in ("failed", "rolled_back"):
                raise SystemExit(
                    f"rollout hit {snap['stage']} while waiting for "
                    f"{want}: {snap['trail']}")
            time.sleep(0.1)
        raise SystemExit(
            f"rollout never reached {want} "
            f"(stuck at {snap and snap['stage']}): {snap and snap['trail']}")

    def p50(rows):
        xs = sorted(rows)
        return xs[len(xs) // 2]

    # warm-up / baseline window: steady traffic with no rollout running
    deadline = time.time() + 60
    while True:
        with lock:
            n = len(records)
        if n >= 30:
            break
        if time.time() > deadline:
            raise SystemExit(f"only {n} routed requests in 60s")
        time.sleep(0.05)

    gen_before = {n: deploy_report(n) for n in ("m1", "m2", "c1")}
    for n, rep in gen_before.items():
        assert rep["engineInstanceId"] == INC, (n, rep)

    # ---- drill one: a clean candidate must auto-promote ----------------
    rollout_started = time.time()
    post_rollout({
        "engineInstanceId": CAND1,
        "targets": f"127.0.0.1:{ports['c1']}",
        "by": "smoke",
        "shadowRate": 1.0, "shadowMinSamples": 8, "shadowHoldSeconds": 1.5,
        "mismatchLimit": 0.2, "scoreTolerance": 0.25,
        "canaryFraction": 0.5, "canaryHoldSeconds": 0.5,
        "canaryMinRequests": 5, "judgeIntervalSeconds": 0.25,
    })
    snap = wait_stage("promoted", 150)
    signals = [e["signal"] for e in snap["trail"]]
    assert signals == ["start", "candidate_verified", "shadow_clean",
                       "canary_clean", "all_verified"], snap["trail"]
    assert snap["stageCode"] == 5, snap["stageCode"]
    assert snap["incumbentInstance"] == INC, snap["incumbentInstance"]
    assert snap["shadow"]["samples"] >= 8, snap["shadow"]
    assert snap["shadow"]["mismatches"] == 0, snap["shadow"]
    assert snap["canary"]["requests"] >= 5, snap["canary"]
    assert snap["judge"]["ticks"] >= 1, snap["judge"]

    # generation flipped exactly once per member, only on a verified 200
    for n in ("m1", "m2", "c1"):
        rep = deploy_report(n)
        assert rep["engineInstanceId"] == CAND1, (n, rep)
        assert rep["generation"] == gen_before[n]["generation"] + 1, (
            n, gen_before[n]["generation"], rep["generation"])

    # shadow mirroring must not move the incumbent's client p50: compare
    # the shadow-stage window against the pre-rollout baseline (generous
    # noise floor — the mirror thread is off the relay path entirely)
    by_stage = {e["to"]: e["at"] for e in snap["trail"]}
    with lock:
        done = list(records)
    base_rows = [el for at, el, c in done
                 if c == 200 and at < rollout_started]
    shadow_rows = [el for at, el, c in done
                   if c == 200 and by_stage["shadow"] <= at
                   < by_stage["canary"]]
    assert len(base_rows) >= 10 and len(shadow_rows) >= 5, (
        len(base_rows), len(shadow_rows))
    base_p50, shadow_p50 = p50(base_rows), p50(shadow_rows)
    assert shadow_p50 <= base_p50 * 3 + 0.08, (
        f"shadow mirroring moved the incumbent p50: baseline "
        f"{base_p50 * 1e3:.1f}ms -> shadow {shadow_p50 * 1e3:.1f}ms")

    # ---- drill two: SIGKILL the candidate mid-canary -------------------
    base2 = {n: deploy_report(n) for n in ("m1", "m2")}
    post_rollout({
        "engineInstanceId": CAND2,
        "targets": f"127.0.0.1:{ports['c2']}",
        "by": "smoke",
        "shadowRate": 1.0, "shadowMinSamples": 5, "shadowHoldSeconds": 0.2,
        "mismatchLimit": 0.2, "scoreTolerance": 0.25,
        "canaryFraction": 0.5, "canaryHoldSeconds": 120.0,
        "canaryMinRequests": 1000000, "judgeIntervalSeconds": 0.25,
        "downAfterFailures": 3,
    })
    wait_stage("canary", 90)
    time.sleep(0.6)  # let the canary keyspace take real traffic
    os.kill(procs["c2"].pid, signal.SIGKILL)
    procs["c2"].wait()
    killed_at = time.time()
    snap2 = wait_stage("rolled_back", 30)

    trail2 = snap2["trail"]
    back = [e for e in trail2 if e["to"] == "rolling_back"]
    assert back and back[0]["signal"] == "candidate_unreachable", trail2
    assert back[0]["at"] - killed_at < 15, (
        f"rollback took {back[0]['at'] - killed_at:.1f}s after the kill")
    assert trail2[-1]["signal"] == "incumbent_restored", trail2
    assert snap2["incumbentInstance"] == CAND1, snap2["incumbentInstance"]

    # the incumbent ring must be byte-identically where the rollout
    # found it: same instance, same swap generation, same sha set
    for n in ("m1", "m2"):
        rep = deploy_report(n)
        assert rep == base2[n], (n, base2[n], rep)

    stop_load.set()
    for t in threads:
        t.join(timeout=30)

    bad = [r for r in records if r[2] != 200]
    assert not bad, (
        f"{len(bad)}/{len(records)} client requests failed across the "
        f"two rollout drills: {bad[:5]} (want zero interactive non-200)")

    with urllib.request.urlopen(rbase + "/metrics", timeout=5) as r:
        metrics = r.read().decode("utf-8")
    for fam in ("pio_tpu_rollout_stage",
                "pio_tpu_rollout_transitions_total{",
                "pio_tpu_rollout_mirrored_total{",
                "pio_tpu_rollout_shadow_samples_total{",
                "pio_tpu_rollout_judge_total{"):
        assert fam in metrics, f"/metrics missing {fam}"

    print(f"rollout stage: clean candidate promoted "
          f"({snap['shadow']['samples']} shadow samples, "
          f"{snap['canary']['requests']} canaried, p50 "
          f"{base_p50 * 1e3:.1f}ms -> {shadow_p50 * 1e3:.1f}ms), "
          f"SIGKILLed candidate rolled back in "
          f"{back[0]['at'] - killed_at:.1f}s, "
          f"{len(records)} client requests, 0 failed")
finally:
    _cleanup()
PY
PYTHONPATH="$PWD${PYTHONPATH:+:$PYTHONPATH}" python "$ROLLOUT_STAGE" "$WORKDIR" \
    || fail "progressive rollout stage (promote/rollback/trail assertions)"
echo "ok   progressive delivery: clean candidate auto-promoted, SIGKILLed candidate auto-rolled-back, zero failed requests"

echo "smoke OK"
