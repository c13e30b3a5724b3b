"""Distributed KVStore: multi-host data parallelism over jax.distributed.

Reference: src/kvstore/kvstore_dist.h:49 (worker: ZPush/ZPull to key-sharded
ps-lite servers), kvstore_dist_server.h:113 (sync/async server with
server-side optimizer), launched by tools/launch.py with
DMLC_ROLE/DMLC_PS_ROOT_URI env vars.

TPU-native redesign (SURVEY §5): there are no server processes.  N identical
workers join one jax.distributed job (coordinator = the reference's
scheduler role, but only for bring-up); `push` allreduces gradients across
processes with collectives over DCN/ICI, `pull` reads the locally-updated
replica.  sync semantics come from the collective itself (every worker
blocks in the same allreduce — the reference's sync-mode barrier,
kvstore_dist_server.h:427, is implicit).  `dist_async` maps to sync
collectives too (straggler tolerance via PS has no collective analog; see
SURVEY §7 hard part (d)).

Env contract (launch.py sets these; DMLC_* names kept for CLI compat):
  DMLC_PS_ROOT_URI / DMLC_PS_ROOT_PORT -> coordinator address
  DMLC_NUM_WORKER                      -> process count
  DMLC_WORKER_ID                       -> process id
"""
from __future__ import annotations

import atexit
import logging
import os
import threading
import time

from .base import MXNetError
from .kvstore import KVStore
from .ndarray import NDArray

__all__ = ["KVStoreDist", "init_distributed"]

_initialized = False


class _Heartbeat(object):
    """Worker failure detector over the jax.distributed coordination KV.

    Reference: src/kvstore/kvstore_dist.h:112-117 — ps-lite heartbeats let
    the scheduler detect dead nodes.  Collectives have no server to notice
    a death: a killed worker leaves every peer BLOCKED inside the
    allreduce forever.  This watchdog gives the fail-stop the docs promise:
    each worker publishes a sequence of heartbeat keys; one checker thread
    per peer waits for the next expected key with a bounded timeout and, on
    a miss without a clean-shutdown marker, records the peer dead and
    aborts the process (os._exit) so the job fails loudly instead of
    hanging.  Enabled by MXNET_KVSTORE_HEARTBEAT_INTERVAL > 0.
    """

    def __init__(self, rank, size, interval, miss_limit=5, fail_stop=True):
        from jax._src import distributed as _jaxdist
        self._client = _jaxdist.global_state.client
        self._rank = rank
        self._size = size
        self._interval = interval
        self._miss = miss_limit
        self._fail_stop = fail_stop
        self.dead = set()
        self._stop = threading.Event()
        self._threads = []
        t = threading.Thread(target=self._beat, daemon=True,
                             name="kv-heartbeat")
        t.start()
        self._threads.append(t)
        for peer in range(size):
            if peer == rank:
                continue
            t = threading.Thread(target=self._watch, args=(peer,),
                                 daemon=True, name="kv-watch-%d" % peer)
            t.start()
            self._threads.append(t)
        atexit.register(self.close)

    def _key(self, rank, seq):
        return "mxkv_hb/%d/%d" % (rank, seq)

    def _beat(self):
        # retire beats older than the declare-dead window (+ bring-up
        # grace) so the coordinator KV store stays bounded for the life of
        # a multi-day job; watchers never lag that far behind a live peer
        keep = max(4 * self._miss, int(60.0 / self._interval)) + 4
        seq = 0
        failures = 0
        while not self._stop.is_set():
            try:
                self._client.key_value_set(self._key(self._rank, seq), "1")
                failures = 0
                if seq >= keep:
                    try:
                        self._client.key_value_delete(
                            self._key(self._rank, seq - keep))
                    except Exception:
                        pass
                seq += 1
            except Exception:
                # transient coordination-service hiccup must not silence a
                # HEALTHY worker's heartbeat (peers would fail-stop a live
                # job); retry, giving up only when persistently broken —
                # at which point the collectives are dead anyway
                failures += 1
                if failures > self._miss:
                    return
            self._stop.wait(self._interval)

    def _watch(self, peer):
        # short wait slices so this thread notices _stop within ~1s —
        # a thread parked in a long native wait at interpreter shutdown
        # aborts the process ("FATAL: exception not rethrown")
        seq = 0
        window = self._miss * self._interval
        slice_ms = max(100, int(min(1.0, self._interval) * 1000))
        deadline = time.monotonic() + max(window, 30.0)  # grace for bring-up
        while not self._stop.is_set():
            try:
                self._client.blocking_key_value_get(self._key(peer, seq),
                                                    slice_ms)
                seq += 1
                deadline = time.monotonic() + window
                continue
            except Exception:
                if self._stop.is_set():
                    return
                try:  # clean shutdown marker?
                    self._client.blocking_key_value_get(
                        "mxkv_hb/%d/done" % peer, 50)
                    return  # peer exited cleanly
                except Exception:
                    pass
                if time.monotonic() < deadline:
                    continue
                self.dead.add(peer)
                logging.error(
                    "kvstore heartbeat: worker %d missed %d beats — "
                    "declaring it dead; fail-stop abort", peer, self._miss)
                if self._fail_stop:
                    os._exit(42)
                return

    def close(self):
        if self._stop.is_set():
            return
        self._stop.set()
        try:
            self._client.key_value_set("mxkv_hb/%d/done" % self._rank, "1")
        except Exception:
            pass
        for t in self._threads:
            t.join(timeout=3.0)


def init_distributed():
    """Join the jax.distributed job described by the env (idempotent).

    Raises instead of degrading: a worker that silently comes up as a
    1-process job would train standalone while the launcher believes it is
    aggregating — fail-stop is the only safe behavior.
    """
    global _initialized
    if _initialized:
        return True
    import jax
    uri = os.environ.get("DMLC_PS_ROOT_URI")
    n = int(os.environ.get("DMLC_NUM_WORKER", "1"))
    if uri is None or n <= 1:
        return False
    port = os.environ.get("DMLC_PS_ROOT_PORT", "9000")
    pid = int(os.environ.get("DMLC_WORKER_ID", "0"))
    jax.distributed.initialize(coordinator_address="%s:%s" % (uri, port),
                               num_processes=n, process_id=pid)
    got = jax.process_count()
    if got != n:
        # tear down before raising so a caller that catches and retries
        # sees this message again, not 'already initialized'
        try:
            jax.distributed.shutdown()
        except Exception:  # noqa: BLE001 - the raise below is the story
            pass
        raise MXNetError(
            "jax.distributed came up with %d processes but the launcher "
            "promised DMLC_NUM_WORKER=%d — refusing to run a silently "
            "degraded 'distributed' job" % (got, n))
    _initialized = True
    return True


class KVStoreDist(KVStore):
    """Multi-process synchronous data-parallel store."""

    def __init__(self, name="dist_sync"):
        super().__init__(name)
        self._multi = init_distributed()
        import jax
        self._rank = jax.process_index() if self._multi else 0
        self._size = jax.process_count() if self._multi else 1
        self._psum_cache = {}
        self._mesh = None
        self._heartbeat = None
        self._rank_snapshotter = None
        self._start_rank_telemetry()
        if self._multi:
            import numpy as np
            from jax.sharding import Mesh
            devs = np.array(jax.devices())
            self._mesh = Mesh(devs.reshape(self._size, -1), ("proc", "local"))
            from . import config
            interval = config.get("MXNET_KVSTORE_HEARTBEAT_INTERVAL")
            if interval > 0:
                self._heartbeat = _Heartbeat(
                    self._rank, self._size, interval,
                    miss_limit=config.get("MXNET_KVSTORE_HEARTBEAT_MISS"))

    def _start_rank_telemetry(self):
        """Cross-host observability (MXNET_TELEMETRY_SHARED_DIR): each
        rank periodically publishes its registry snapshot as
        ``telemetry_rank<N>.json`` under a shared directory, so
        ``tools/telemetry_dump.py aggregate`` can merge the whole tier
        into one rank-labeled document — the per-replica numbers this
        tier had were useless for spotting a straggler until they were
        joinable in one place.  Advisory: a failure to start the
        pusher must never fail the kvstore."""
        from . import config, telemetry
        shared = config.get("MXNET_TELEMETRY_SHARED_DIR")
        if not shared or not telemetry.enabled():
            return
        try:
            self._rank_snapshotter = telemetry.start_rank_snapshotter(
                shared, self._rank)
            atexit.register(self._stop_rank_telemetry)
        except Exception as e:
            logging.warning(
                "kvstore rank-telemetry pusher failed to start: %s", e)

    def _stop_rank_telemetry(self):
        snap, self._rank_snapshotter = self._rank_snapshotter, None
        if snap is not None:
            snap.stop()          # writes one final snapshot

    def get_num_dead_node(self, node_id=0):
        """Real failure detection when the heartbeat watchdog is on
        (MXNET_KVSTORE_HEARTBEAT_INTERVAL > 0); otherwise the fail-stop
        contract of the base class holds (a hung/dead peer aborts the
        job)."""
        if self._heartbeat is not None:
            return len(self._heartbeat.dead)
        return super().get_num_dead_node(node_id)

    @property
    def rank(self):
        return self._rank

    @property
    def num_workers(self):
        return self._size

    def _allreduce(self, jax_array):
        """Cross-process sum as ONE compiled collective: each process's
        device-resident gradient becomes its shard on the 'proc' mesh axis
        (device-to-device placement, no host copy) and a jitted sum-over-proc
        with replicated output runs the allreduce on-device (DCN between
        hosts, ICI within)."""
        if not self._multi:
            return jax_array
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        in_sharding = NamedSharding(self._mesh, P("proc"))
        key = (tuple(jax_array.shape), str(jax_array.dtype))
        fn = self._psum_cache.get(key)
        if fn is None:
            fn = jax.jit(lambda x: x.sum(axis=0),
                         out_shardings=NamedSharding(self._mesh, P()))
            self._psum_cache[key] = fn
        local = jax_array[None]
        global_shape = (self._size,) + tuple(jax_array.shape)
        shards = [jax.device_put(local, d)
                  for d in in_sharding.addressable_devices]
        stacked = jax.make_array_from_single_device_arrays(
            global_shape, in_sharding, shards)
        summed = fn(stacked)
        # fully-replicated output: every process holds the complete value
        return summed.addressable_shards[0].data

    def _reduce_global(self, key, merged):
        if not self._multi:
            return merged
        from .ndarray.ndarray import _wrap
        from .ndarray.sparse import RowSparseNDArray
        if isinstance(merged, RowSparseNDArray):
            # cross-process rsp reduce: collectives need static shapes, so
            # the WIRE is dense (an O(rows*cols) allreduce — a compressed
            # variable-nnz union over DCN is future work), but the result
            # re-compresses before the updater so the rsp lazy-update
            # semantics (only touched rows move) stay IDENTICAL to the
            # single-process path.  Note: a row summing exactly to zero
            # across workers drops out of the union, like the reference's
            # server-side retain of nonzero rows.
            dense = self._allreduce(merged.tostype("default")._data)
            return _wrap(dense, merged.context).tostype("row_sparse")
        return _wrap(self._allreduce(merged._data), merged._ctx)

    def init(self, key, value):
        super().init(key, value)
        # rank0's initial weights win, as in the reference (workers pull the
        # server-held init): broadcast by averaging identical inits is wrong
        # when seeds differ, so ship rank0's values
        if self._multi:
            from jax.experimental import multihost_utils
            from .ndarray.sparse import BaseSparseNDArray
            for k in (key if isinstance(key, (list, tuple)) else [key]):
                v = self._store[k]
                if isinstance(v, BaseSparseNDArray):
                    # broadcast the compressed aux arrays; the dense _data
                    # setter is (rightly) forbidden on sparse storage
                    for name, arr in v._aux.items():
                        arr._data = multihost_utils.broadcast_one_to_all(
                            arr._data)
                else:
                    v._data = multihost_utils.broadcast_one_to_all(v._data)

    def barrier(self):
        if self._multi:
            from jax.experimental import multihost_utils
            multihost_utils.sync_global_devices("kvstore_barrier")
        else:
            super().barrier()
