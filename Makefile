# Unified build entry points (the L0 role of the reference's bazel
# tree): native object store + transfer plane, C++ driver API, wheel.
PY ?= python

.PHONY: all native cpp wheel test smoke obs chaos drain failover \
	elastic ha partition autoscale profile lint lint-fast overload \
	diskfault containment clean

all: native cpp

native: ray_tpu/core/object_store/libtpustore.so

ray_tpu/core/object_store/libtpustore.so: \
		ray_tpu/core/object_store/store.cc \
		ray_tpu/core/object_store/transfer.cc
	g++ -O2 -shared -fPIC -pthread -o $@ $^

cpp:
	$(MAKE) -C ray_tpu/cpp

wheel: native
	$(PY) -m pip wheel --no-deps --no-build-isolation -w dist .

# CPU: tests/conftest.py holds every process to JAX_PLATFORMS=cpu with
# eight virtual devices; Pallas kernels run through the interpreter.
test:
	$(PY) -m pytest tests/ -q -m 'not slow'

# The chip: gpt2-medium trained and served through the normal entry
# points, one process per chip.  Needs a TPU and fails without one
# (from the sandbox: `chiprun -- python chip_smoke.py`); `--chips 4`
# runs the sharded path on a four-chip host instead.
smoke:
	$(PY) chip_smoke.py

# Observability suite: timeline/span propagation, runtime-metrics
# battery, structured events, plus the PR-10 flight-recorder layer —
# per-RPC attribution, metrics history, incident bundles, clock-offset
# timeline merge, metrics lint (all tier-1 — no `slow` markers).
obs:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_observability.py \
		tests/test_runtime_metrics.py tests/test_events.py \
		tests/test_control_plane_obs.py -q

# Chaos suite: seeded fault-injection units + all four end-to-end
# recovery scenarios (each runs twice with the same seeds — injection
# is deterministic).  Includes the `slow`-marked multi-process
# scenarios tier-1 skips.
chaos:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_chaos.py \
		tests/test_controller_ft.py -q

# Storage-fault suite (PR-18): filesystem chaos sites (WAL / spill /
# checkpoint / flight-recorder), WAL-poison self-fence -> standby
# promotion, spill CRC + ENOSPC backpressure, checkpoint keep-previous,
# disk-health watermarks, and the fn_lost re-registration path.
diskfault:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_diskfault.py -q

containment:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_containment.py -q

# Overload-protection suite (PR-17): priority RPC lanes, watermark
# state machine + admission shedding, credit flow control, bounded
# pubsub, kv-blob divert, and the tier-1 brownout soak.
overload:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_overload.py -q

# Drain suite: graceful-node-drain units + end-to-end phased
# evacuation, including the `slow` chaos variants (drain under serve
# traffic, injected evacuation failure -> lineage fallback).
drain:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_drain.py -q

# Failover suite: decode-stream failover — replay-journal/seq-dedupe
# units, teacher-forced resume parity, chaos mid-stream replica kill
# with byte-identical recovery, and the `slow` multi-node drain of a
# node hosting live streams (zero dropped sessions).
failover:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_failover.py -q

# Elastic suite: unannounced-failure gang repair — crash-safe
# checkpoint registration, pubsub death/drain signal units, the hard
# node-kill acceptance scenario (fast repair, loss parity, ×2 seeds),
# and the `slow` chaos-abort / double-kill fallback cases.
elastic:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_elastic.py -q

# Controller HA suite: WAL CRC/replication units, split-brain epoch
# fencing, in-process promotion, the end-to-end kill-the-leader
# acceptance scenario (tables intact, in-flight wave completes, ×2
# seeds), chaos-severed replication -> bounded-lag async degrade, and
# the `slow` leader-death-mid-drain / mid-elastic-repair resumptions.
ha:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_controller_ha.py \
		tests/test_controller_ft.py -q

# Partition suite: gray-failure handling — connectivity-matrix fold
# units (asymmetric / controller-only / full partitions), the
# alternate-path fetch ladder, suspect/quarantine end to end
# (controller-link blackhole keeps the node SUSPECT, its actor
# survives, zero-restart rejoin ×2 seeds; grace exhaustion dies), and
# the `slow` asymmetric A↛B transfer partition under a task wave
# completing via the relay rung ×2 seeds.
partition:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_partition.py -q

# Static analysis in one shot: the framework-invariant suite — all
# eight rules (PR-13: loop-blocking / thread-race / chaos-site /
# WAL-op / RPC-surface; PR-14: rpc-payload-contract / lock-order /
# wal-replay-determinism) in ONE invocation against the committed
# baseline — plus the PR-10 metrics lint.  Offline: no cluster, no
# JAX; both gate tier-1.
lint:
	$(PY) -m ray_tpu.scripts.cli lint
	$(PY) -m ray_tpu.scripts.cli metrics lint

# Pre-commit fast path: full registries, findings filtered to files
# git considers changed.
lint-fast:
	$(PY) -m ray_tpu.scripts.cli lint --changed

# Autoscale suite: pure policy units (trend/hysteresis/cooldown/SUSPECT
# down-weight/victim pick), prefix-trie units, engine shared-prefix
# admission parity, controller loop + chaos-dropped-decision retry,
# router prefix affinity, per-deployment metrics-history filter.
autoscale:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_serve_autoscale.py -q

# Dispatch-profiler / tracing suite: wrap-once shims, compile ledger,
# MFU table, per-request TTFT/ITL propagation, breakdown coverage,
# compile-storm + SLO-breach triggers.
profile:
	JAX_PLATFORMS=cpu $(PY) -m pytest tests/test_device_profile.py \
		tests/test_serve_breakdown.py -q

clean:
	rm -f ray_tpu/core/object_store/libtpustore.so dist/*.whl
	$(MAKE) -C ray_tpu/cpp clean
