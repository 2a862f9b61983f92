"""Gang of training worker actors under one placement group.

Capability mirror of the reference's `train/_internal/worker_group.py:92,186`
(`WorkerGroup` spawning actor workers, `execute`/`execute_async` on all).
TPU-first difference: the gang is placed with topology-aware bundles so each
worker owns one TPU host's chips, and worker metadata carries device/slice
info for mesh bring-up.
"""

from __future__ import annotations

import socket
import threading
from typing import Any, Callable, Dict, List, Optional

from .. import api
from ..util.placement_group import PlacementGroup, placement_group, \
    remove_placement_group
from ..util.scheduling_strategies import PlacementGroupSchedulingStrategy


class TrainWorker:
    """Actor hosting one rank of the training gang.  The train function runs
    on a session thread so actor methods stay responsive for result polling
    (the reference's session-thread design, `train/_internal/session.py`)."""

    def __init__(self, rank_env: Dict[str, Any]):
        import os
        for k, v in (rank_env or {}).items():
            os.environ[str(k)] = str(v)
        self._thread = None
        self._session = None
        self._error: Optional[BaseException] = None

    def metadata(self) -> Dict[str, Any]:
        import json
        import os
        node_id = None
        ctx = os.environ.get("RAY_TPU_WORKER_CONTEXT")
        if ctx:
            try:
                node_id = json.loads(ctx).get("node_id")
            except ValueError:
                pass
        return {"hostname": socket.gethostname(), "pid": os.getpid(),
                "node_id": node_id}

    def execute(self, fn_bytes: bytes, *args, **kwargs):
        from ..core.serialization import loads_function
        fn = loads_function(fn_bytes)
        return fn(*args, **kwargs)

    def init_session(self, *, world_rank: int, local_rank: int,
                     world_size: int, node_rank: int,
                     trial_name: str = "train",
                     checkpoint_bytes: Optional[bytes] = None,
                     dataset_shard=None,
                     elastic: Optional[Dict[str, Any]] = None,
                     start_iteration: int = 0):
        from ..air.checkpoint import Checkpoint
        from ..air.session import _Session, _set_session
        self._session = _Session(
            world_rank=world_rank, local_rank=local_rank,
            world_size=world_size, node_rank=node_rank,
            trial_name=trial_name, dataset_shard=dataset_shard)
        if checkpoint_bytes is not None:
            self._session.last_checkpoint = Checkpoint.from_bytes(
                checkpoint_bytes)
        # a repair-spawned replacement resumes mid-run: its report
        # iterations must continue from the restored snapshot step
        self._session.iteration = int(start_iteration)
        if elastic:
            from .elastic import ElasticSnapshotter
            self._session.elastic = ElasticSnapshotter(
                run_id=elastic["run_id"], world_rank=world_rank,
                interval=elastic.get("interval", 10),
                keep=elastic.get("keep", 2))
        # install on the actor main thread as well: backend setup fns run
        # there (via execute) and need ranks / a place to hang the mesh
        _set_session(self._session)

    def start_training(self, fn_bytes: bytes, config: Dict[str, Any]):
        import threading

        from ..core.serialization import loads_function
        from ..air.session import _set_session
        train_fn = loads_function(fn_bytes)
        session = self._session

        def run():
            import inspect

            from ..core.worker_runtime import mark_actor_init
            _set_session(session)
            mark_actor_init(trial=session.trial_name,
                            rank=str(session.world_rank))
            try:
                if inspect.signature(train_fn).parameters:
                    train_fn(config)
                else:
                    train_fn()
            except SystemExit:
                pass
            except BaseException as e:  # surfaced via finish()
                self._error = e
            finally:
                session.queue.put(None)  # sentinel: training done

        self._error = None
        self._finished = False
        self._thread = threading.Thread(target=run, daemon=True)
        self._thread.start()
        return True

    def next_result(self, timeout_s: float = 10.0):
        """One queued report (metrics + optional checkpoint bytes), the
        sentinel None when training ended, or "__timeout__".  Completion is
        latched: after the sentinel has been seen once, every later poll
        returns None immediately — ranks that finish (or fail) early must
        not turn into perpetual "__timeout__"s that keep the executor's
        all-None termination condition unreachable."""
        import queue as _q
        if getattr(self, "_finished", False):
            return None
        try:
            item = self._session.queue.get(timeout=timeout_s)
        except _q.Empty:
            return "__timeout__"
        if item is None:
            self._finished = True
            return None
        ckpt = item.get("checkpoint")
        if ckpt is not None:
            item = dict(item, checkpoint=ckpt.to_bytes())
        return item

    def finish(self):
        if self._thread is not None:
            self._thread.join()
        if self._error is not None:
            import traceback
            raise RuntimeError("train function failed: " + "".join(
                traceback.format_exception(self._error)))
        return True

    def reset_for_repair(self, checkpoint_bytes: bytes, iteration: int,
                         join_timeout_s: float = 10.0) -> bool:
        """Park this healthy rank for an elastic gang repair: stop the
        running train thread (it exits at its next ``session.report``),
        rewind the session to the restored snapshot, and leave the actor
        ready for a fresh ``start_training`` — WITHOUT killing the actor
        or re-running placement.  False (thread refused to stop inside
        the budget, e.g. blocked in a collective with the dead rank)
        sends the executor to the full-restart fallback."""
        import queue as _q

        from ..air.checkpoint import Checkpoint
        s = self._session
        if s is None:
            return False
        s.stop_event.set()
        if self._thread is not None:
            self._thread.join(timeout=max(0.0, join_timeout_s))
            if self._thread.is_alive():
                return False
            self._thread = None
        # drop reports from the abandoned timeline (incl. the sentinel
        # the stopping thread's finally pushed)
        while True:
            try:
                s.queue.get_nowait()
            except _q.Empty:
                break
        if s.elastic is not None:
            # a queued-but-unwritten snapshot is from the abandoned
            # timeline too — registering it after the rewind would
            # advertise state the new timeline may never reproduce
            try:
                s.elastic._q.get_nowait()
            except _q.Empty:
                pass
        s.stop_event = threading.Event()
        s.last_checkpoint = Checkpoint.from_bytes(checkpoint_bytes)
        s.iteration = int(iteration)
        s._last_report_t = None
        self._error = None
        self._finished = False
        return True

    def stop_session(self):
        if self._session is not None:
            self._session.stop_event.set()
            if self._session.elastic is not None:
                self._session.elastic.stop()
        return True

    def shutdown(self):
        return True


class WorkerGroup:
    """N TrainWorker actors gang-scheduled under one placement group."""

    def __init__(self, num_workers: int,
                 resources_per_worker: Optional[Dict[str, float]] = None,
                 placement_strategy: str = "PACK",
                 rank_env: Optional[Dict[str, Any]] = None):
        self.num_workers = num_workers
        bundles = []
        for _ in range(num_workers):
            b = dict(resources_per_worker or {})
            b.setdefault("CPU", 1.0)
            bundles.append(b)
        self._bundles = bundles
        self._rank_env = rank_env or {}
        self.pg: PlacementGroup = placement_group(
            bundles, strategy=placement_strategy)
        self.pg.ready()
        actor_cls = api.remote(TrainWorker)
        self.workers = []
        for i in range(num_workers):
            strategy = PlacementGroupSchedulingStrategy(
                placement_group=self.pg, placement_group_bundle_index=i)
            self.workers.append(
                actor_cls.options(
                    scheduling_strategy=strategy,
                    num_cpus=bundles[i].get("CPU", 1.0),
                    # the rest of the bundle too: a worker that does not
                    # ASK for its bundle's TPU is leased as CPU work and
                    # never gets the chip (core/accelerator.py)
                    resources={k: v for k, v in bundles[i].items()
                               if k != "CPU"},
                ).remote(self._rank_env))

    def spawn_replacement(self, index: int):
        """Replace a dead gang member with a fresh actor OUTSIDE the
        placement group (its bundle sits on the dead node): the
        scheduler places it on whatever spare capacity exists.  The old
        handle is dropped; callers re-init the session themselves."""
        actor_cls = api.remote(TrainWorker)
        w = actor_cls.options(
            num_cpus=self._bundles[index].get("CPU", 1.0),
            resources={k: v for k, v in self._bundles[index].items()
                       if k != "CPU"},
        ).remote(self._rank_env)
        self.workers[index] = w
        return w

    def execute(self, fn: Callable, *args, **kwargs) -> List[Any]:
        """Run fn on every worker, return per-rank results."""
        from ..core.serialization import dumps_function
        blob = dumps_function(fn)
        refs = [w.execute.remote(blob, *args, **kwargs)
                for w in self.workers]
        return api.get(refs, timeout=600.0)

    def execute_single(self, index: int, fn: Callable, *args, **kwargs):
        from ..core.serialization import dumps_function
        return api.get(self.workers[index].execute.remote(
            dumps_function(fn), *args, **kwargs), timeout=600.0)

    def metadata(self) -> List[Dict[str, Any]]:
        return api.get([w.metadata.remote() for w in self.workers],
                       timeout=60.0)

    def shutdown(self):
        for w in self.workers:
            try:
                api.kill(w)
            except Exception:
                pass
        try:
            remove_placement_group(self.pg)
        except Exception:
            pass
        self.workers = []
