"""Collective backends: XLA (jax.distributed) and KV (control-plane).

Rendezvous protocol (both backends): rank 0 publishes group metadata at
``collective/<group>/meta`` in the runtime KV store; every member then
checks in at ``collective/<group>/join/<rank>``.  This replaces the
reference's named-store-actor NCCL-unique-id exchange (reference:
python/ray/util/collective/collective_group/nccl_collective_group.py:36).
"""

from __future__ import annotations

import pickle
import socket
import time
from typing import Any

_RENDEZVOUS_TIMEOUT_S = 120.0
_POLL_S = 0.02


def _kv_put(key: str, value: bytes) -> None:
    from .._private.api import _control
    _control("kv_put", key, value)


def _kv_get(key: str):
    from .._private.api import _control
    return _control("kv_get", key)


def _kv_del(key: str) -> None:
    from .._private.api import _control
    _control("kv_del", key)


def _wait_for(key: str, timeout: float = _RENDEZVOUS_TIMEOUT_S) -> bytes:
    """Blocking server-side wait (controller condvar, ctl_kv_wait) — the
    writer's kv_put wakes us; no client poll loop.  Chunked so a lost
    reply can't strand the caller past the deadline."""
    deadline = time.monotonic() + timeout
    from .._private.api import _control
    while True:
        remaining = deadline - time.monotonic()
        if remaining <= 0:
            raise TimeoutError(f"rendezvous timed out waiting for {key}")
        v = _control("kv_wait", key, timeout=min(remaining, 10.0))
        if v is not None:
            return v


def _free_port() -> int:
    s = socket.socket()
    s.bind(("127.0.0.1", 0))
    port = s.getsockname()[1]
    s.close()
    return port


class _SocketP2P:
    """Direct rank-to-rank transport for send/recv.

    Replaces the round-1 pickle-over-KV polling path: each rank lazily
    opens a TCP listener (address published once through the KV
    rendezvous), peers keep persistent connections, and frames are
    (src_rank, payload) messages demultiplexed into per-source queues.
    The reference's analog is NCCL p2p inside a group
    (nccl_collective_group.py send/recv); on TPU, device tensors should
    ride ppermute inside jit — this path carries host-side numpy.
    """

    def __init__(self, group_name: str, rank: int):
        self.group = group_name
        self.rank = rank
        self.token: bytes = b""
        self._listener = None
        self._out: dict = {}          # dst rank -> Connection
        self._in_queues: dict = {}    # src rank -> queue.Queue
        self._qlock = None
        self._closed = False

    # -- wiring -------------------------------------------------------------

    def _addr_key(self, rank: int) -> str:
        return f"collective/{self.group}/p2p_addr/{rank}"

    def _ensure_token(self) -> None:
        """Group transport secret, minted by rank 0 and distributed over
        the cluster's authenticated control channel (the KV store) — the
        listener unpickles peer frames, so a guessable key would be remote
        code execution for anyone who can reach the port."""
        if self.token:
            return
        key = f"collective/{self.group}/p2p_token"
        if self.rank == 0:
            import os as _os
            self.token = _os.urandom(16)
            _kv_put(key, self.token)
        else:
            self.token = bytes(_wait_for(key))

    def ensure_listener(self) -> None:
        if self._listener is not None:
            return
        import os
        import threading
        from multiprocessing.connection import Listener
        self._ensure_token()
        self._qlock = threading.Lock()
        # Bind the wildcard but advertise a peer-reachable host so ranks
        # on different nodes can connect (same convention as the cluster
        # data plane, cluster.py DataServer).
        self._listener = Listener(("0.0.0.0", 0), authkey=self.token)
        advertise = os.environ.get("RAY_TPU_ADVERTISE_HOST", "127.0.0.1")
        _kv_put(self._addr_key(self.rank),
                pickle.dumps((advertise, self._listener.address[1])))
        self._acceptor = threading.Thread(
            target=self._accept_loop, daemon=True,
            name=f"p2p-accept-{self.group}-{self.rank}")
        self._acceptor.start()

    def _accept_loop(self) -> None:
        import threading
        while not self._closed:
            try:
                conn = self._listener.accept()
            except Exception:
                # A peer dying mid-handshake must not kill the accept
                # loop; only exit when this endpoint is closing.
                if self._closed:
                    return
                continue
            if self._closed:
                try:
                    conn.close()
                except Exception:
                    pass
                return
            from .._private import sanitizer
            sanitizer.spawn(self._reader, args=(conn,),
                            name="collective-reader")

    def _reader(self, conn) -> None:
        import queue as _q
        while not self._closed:
            try:
                src, payload = conn.recv()
            except (EOFError, OSError):
                return
            with self._qlock:
                q = self._in_queues.setdefault(src, _q.Queue())
            q.put(payload)

    def send(self, dst_rank: int, payload: bytes) -> None:
        from multiprocessing.connection import Client
        conn = self._out.get(dst_rank)
        if conn is None:
            self._ensure_token()
            addr = pickle.loads(_wait_for(self._addr_key(dst_rank)))
            conn = Client(tuple(addr), authkey=self.token)
            self._out[dst_rank] = conn
        conn.send((self.rank, payload))

    def recv(self, src_rank: int,
             timeout: float = _RENDEZVOUS_TIMEOUT_S) -> bytes:
        import queue as _q
        self.ensure_listener()
        with self._qlock:
            q = self._in_queues.setdefault(src_rank, _q.Queue())
        try:
            return q.get(timeout=timeout)
        except _q.Empty:
            raise TimeoutError(
                f"p2p recv from rank {src_rank} timed out") from None

    def close(self) -> None:
        self._closed = True
        for conn in self._out.values():
            try:
                conn.close()
            except Exception:
                pass
        if self._listener is not None:
            # Unblock + join the acceptor before closing the fd (see
            # cluster._drain_acceptor: a blocked accept on a closed fd can
            # adopt a reused fd and steal a newer listener's handshakes).
            from .._private.cluster import _drain_acceptor
            _drain_acceptor(self._listener, self._acceptor)
            try:
                self._listener.close()
            except Exception:
                pass
            _kv_del(self._addr_key(self.rank))
        if self.rank == 0 and self.token:
            _kv_del(f"collective/{self.group}/p2p_token")


class XlaBackend:
    """Group ops lower to XLA collectives over a jax.distributed world.

    On CPU the world uses gloo; on TPU the mesh forms over ICI/DCN via
    libtpu (the JaxTrainer seam, reference: train/v2/jax/config.py:115-133).
    jax.distributed supports one world per process: one XlaBackend group
    may be active at a time in a given worker.
    """

    def __init__(self, world_size: int, rank: int, group_name: str):
        self.world_size = world_size
        self.rank = rank
        self.group_name = group_name
        self._mesh = None
        self._np = None
        # (kind, op, shape, dtype) -> compiled fn.  jit caches by callable
        # identity, so fresh lambdas per call would re-trace every op.
        self._jit_cache: dict = {}
        self._p2p = _SocketP2P(group_name, rank)

    def setup(self) -> None:
        # Open the p2p listener up-front so a peer's first send never has
        # to wait for this rank's first recv to publish the address.
        self._p2p.ensure_listener()
        key = f"collective/{self.group_name}/addr"
        if self.rank == 0:
            addr = f"127.0.0.1:{_free_port()}"
            _kv_put(key, addr.encode())
        else:
            addr = _wait_for(key).decode()

        import jax
        # Must not touch the backend (jax.devices/default_backend) before
        # distributed.initialize.  CPU worlds use gloo, jax's default.
        jax.distributed.initialize(addr, num_processes=self.world_size,
                                   process_id=self.rank)
        import numpy as np
        from jax.sharding import Mesh
        self._np = np
        devs = jax.devices()
        self._mesh = Mesh(np.array(devs), ("world",))
        self._devices_per_proc = len(jax.local_devices())

    def teardown(self) -> None:
        self._p2p.close()
        try:
            import jax
            jax.distributed.shutdown()
        except Exception:
            pass
        if self.rank == 0:
            _kv_del(f"collective/{self.group_name}/addr")

    # -- helpers ------------------------------------------------------------

    def _global(self, local):
        """Local [*, ...] -> global [n_devices, ...] sharded on axis 0.

        With d devices per process the local row appears d times — as a
        zero-copy broadcast view, not a materialized repeat; reductions
        de-duplicate with a stride-d slice so multi-device processes
        contribute once.
        """
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        local = np.ascontiguousarray(local)
        sharding = NamedSharding(self._mesh, P("world"))
        view = np.broadcast_to(local[None],
                               (self._devices_per_proc, *local.shape))
        return jax.make_array_from_process_local_data(sharding, view)

    def _replicated_result(self, kind: str, computation, arr, op: str = ""):
        import jax
        import numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        cache_key = (kind, op, arr.shape, str(arr.dtype))
        fn = self._jit_cache.get(cache_key)
        if fn is None:
            fn = jax.jit(computation,
                         out_shardings=NamedSharding(self._mesh, P()))
            self._jit_cache[cache_key] = fn
        out = fn(arr)
        return np.asarray(out.addressable_shards[0].data)

    @staticmethod
    def _op_fn(op: str):
        import jax.numpy as jnp
        return {"sum": jnp.sum, "prod": jnp.prod, "min": jnp.min,
                "max": jnp.max}[op]

    # -- ops ----------------------------------------------------------------

    def allreduce(self, tensor, op: str = "sum"):
        fn = self._op_fn(op)
        arr = self._global(tensor)
        k = self._devices_per_proc
        return self._replicated_result(
            "allreduce", lambda a: fn(a[::k], axis=0), arr, op)

    def allgather(self, tensor):
        arr = self._global(tensor)
        k = self._devices_per_proc
        return self._replicated_result("allgather", lambda a: a[::k], arr)

    def reducescatter(self, tensor, op: str = "sum"):
        """Input per rank: [world * chunk, ...]; returns this rank's chunk."""
        full = self.allreduce(tensor, op)
        n = full.shape[0]
        if n % self.world_size:
            raise ValueError(
                f"reducescatter dim {n} not divisible by {self.world_size}")
        chunk = n // self.world_size
        return full[self.rank * chunk:(self.rank + 1) * chunk]

    def broadcast(self, tensor, src_rank: int = 0):
        import numpy as np
        local = np.asarray(tensor)
        masked = local if self.rank == src_rank else np.zeros_like(local)
        return self.allreduce(masked, "sum")

    def reduce(self, tensor, dst_rank: int = 0, op: str = "sum"):
        out = self.allreduce(tensor, op)
        import numpy as np
        return out if self.rank == dst_rank else np.asarray(tensor)

    def barrier(self) -> None:
        import numpy as np
        self.allreduce(np.zeros(1, np.float32), "sum")

    def send(self, tensor, dst_rank: int) -> None:
        import numpy as np
        self._p2p.send(dst_rank, pickle.dumps(np.asarray(tensor)))

    def recv(self, shape, dtype, src_rank: int):
        return pickle.loads(self._p2p.recv(src_rank))


class KVBackend:
    """Pure-Python collective over the runtime KV store.

    The gloo-equivalent control-plane fallback (SURVEY §2.4 collectives
    row): correct for any picklable numpy payload, no jax required.  Each
    op round gets a sequence number so groups can run many ops.
    """

    def __init__(self, world_size: int, rank: int, group_name: str):
        self.world_size = world_size
        self.rank = rank
        self.group_name = group_name
        self._seq = 0
        self._nonce = ""
        self._p2p = _SocketP2P(group_name, rank)

    def setup(self) -> None:
        self._p2p.ensure_listener()
        # Rank 0 publishes a fresh incarnation nonce so a recreated group
        # with the same name can never read a previous incarnation's rounds.
        meta_key = f"collective/{self.group_name}/meta"
        if self.rank == 0:
            import uuid
            self._nonce = uuid.uuid4().hex[:8]
            _kv_put(meta_key, self._nonce.encode())
        else:
            self._nonce = _wait_for(meta_key).decode()
        base = f"collective/{self.group_name}/{self._nonce}"
        _kv_put(f"{base}/join/{self.rank}", b"1")
        deadline = time.monotonic() + _RENDEZVOUS_TIMEOUT_S
        for r in range(self.world_size):
            _wait_for(f"{base}/join/{r}", deadline - time.monotonic())

    def teardown(self) -> None:
        self._p2p.close()
        base = f"collective/{self.group_name}/{self._nonce}"
        _kv_del(f"{base}/join/{self.rank}")
        for s in (self._seq, self._seq - 1):
            if s > 0:
                _kv_del(f"{base}/r{s}/{self.rank}")
        if self.rank == 0:
            _kv_del(f"collective/{self.group_name}/meta")

    def _round(self, tensor) -> list:
        """Exchange: everyone publishes, everyone reads all.

        Garbage collection: entering round n proves every rank finished
        round n-1 (we read all its keys), which proves every rank had
        finished reading round n-2 — so each rank deletes its own n-2 key
        here, bounding KV growth to two rounds.
        """
        import numpy as np
        self._seq += 1
        base = f"collective/{self.group_name}/{self._nonce}"
        if self._seq >= 3:
            _kv_del(f"{base}/r{self._seq - 2}/{self.rank}")
        _kv_put(f"{base}/r{self._seq}/{self.rank}",
                pickle.dumps(np.asarray(tensor)))
        parts = []
        for r in range(self.world_size):
            parts.append(pickle.loads(
                _wait_for(f"{base}/r{self._seq}/{r}")))
        return parts

    @staticmethod
    def _reduce(parts: list, op: str):
        import numpy as np
        fns = {"sum": np.add, "prod": np.multiply, "min": np.minimum,
               "max": np.maximum}
        out = parts[0].copy()
        for p in parts[1:]:
            out = fns[op](out, p)
        return out

    def allreduce(self, tensor, op: str = "sum"):
        return self._reduce(self._round(tensor), op)

    def allgather(self, tensor):
        import numpy as np
        return np.stack(self._round(tensor))

    def reducescatter(self, tensor, op: str = "sum"):
        full = self.allreduce(tensor, op)
        if full.shape[0] % self.world_size:
            raise ValueError(
                f"reducescatter dim {full.shape[0]} not divisible by "
                f"{self.world_size}")
        chunk = full.shape[0] // self.world_size
        return full[self.rank * chunk:(self.rank + 1) * chunk]

    def broadcast(self, tensor, src_rank: int = 0):
        parts = self._round(tensor)
        return parts[src_rank]

    def reduce(self, tensor, dst_rank: int = 0, op: str = "sum"):
        out = self.allreduce(tensor, op)
        import numpy as np
        return out if self.rank == dst_rank else np.asarray(tensor)

    def barrier(self) -> None:
        import numpy as np
        self._round(np.zeros(1))

    def send(self, tensor, dst_rank: int) -> None:
        import numpy as np
        self._p2p.send(dst_rank, pickle.dumps(np.asarray(tensor)))

    def recv(self, shape, dtype, src_rank: int):
        return pickle.loads(self._p2p.recv(src_rank))
