#!/usr/bin/env python3
"""The load generator: a process of its own that never imports JAX.

``run.py`` starts it before it touches JAX itself and talks to it over
the pipe: one JSON object a line in, one a line out.  The generator talks
to the stack only over loopback HTTP with the program's own client library
(``controlplane.remote.RemoteClient``) for ``create_many``, and one pod
watch, read as plain JSON lines, to see binds.  Every time it reports is taken on its own clock.

Commands (``op``):

``nodes``   create the configuration's nodes from the seed
``init``    open the pod watch, create the init pods, wait for their binds
``burst``   create ``count`` measured pods, at once or in creates of
            ``chunk``, and wait for their binds
``run``     offer one traffic mix for ``seconds``; answers when the window
            closes (what is outstanding then is left to ``grace``)
``grace``   wait, at most ``seconds``, for every pod sent to be bound
``acks``    the record: every bind the watch has carried (pod -> node, the
            instant it was read, re-binds) and every delete that was
            answered 200 (the instants it was sent and answered)
``stop``    close the watch and exit

The objects come from the configuration's maker: the module under
``makers/`` that ``configs/<name>.json`` names under ``maker``, or
``cluster`` where it names none; it is loaded once, at ``hello``.

A traffic mix is data (``benchmarks/traffic/<mix>.json``); the two loops
below are the one general generator that reads it.  ``closed``: keep
``outstanding`` pods created and not yet seen bound, topping up in
``chunk``s from ``senders`` threads, each on a connection of its own (one
sender blocks in each create, and the stack binds faster than one stream
of creates feeds it: the backlog would never form).  A closed mix that
sets ``live_target`` also runs ``deleters`` threads, each on a connection
of its own: whenever more measured pods than that are seen bound and not
yet deleted, the longest-bound of them is deleted, one ``DELETE`` a pod
(the API has no collection delete); ``live_pod_cap`` then bounds the pods
live and not the pods ever sent.  ``open``: Poisson arrivals at ``rate_per_s``; the gaps are one
fixed sample of the exponential distribution, scaled to the rate, and the
seed only shuffles their order, so that every seed offers the same
arrivals in another order; each pod is timed from the instant it was due.
"""

from __future__ import annotations

import collections
import importlib
import json
import os
import random
import sys
import threading
import time
from typing import Any, Dict, List, Optional

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

#: the fixed sample the open loop's gaps are drawn from (see module doc)
GAP_SAMPLE_SEED = 0xA11CE


def percentile(sorted_values: List[float], q: float) -> Optional[float]:
    """Nearest-rank percentile of an ascending list (None when empty)."""
    if not sorted_values:
        return None
    rank = max(1, -(-len(sorted_values) * q // 100))
    return sorted_values[int(rank) - 1]


def summary_ms(values_s: List[float]) -> Dict[str, Any]:
    vals = sorted(v * 1e3 for v in values_s)
    if not vals:
        return {"n": 0}
    return {
        "n": len(vals),
        "p50": percentile(vals, 50),
        "p95": percentile(vals, 95),
        "p99": percentile(vals, 99),
        "max": vals[-1],
        "mean": sum(vals) / len(vals),
    }


def load_maker(config: Dict[str, Any]) -> Any:
    """The module that makes the configuration's objects."""
    name = config.get("maker")
    return importlib.import_module("makers." + name if name else "cluster")


def arrival_offsets(rate_per_s: float, seconds: float, seed: int) -> List[float]:
    """Offsets from the window's start at which the open loop's pods are
    due: ``round(rate * seconds)`` of them in every seed."""
    n = max(1, round(rate_per_s * seconds))
    sample = random.Random(GAP_SAMPLE_SEED)
    gaps = [sample.expovariate(1.0) for _ in range(n)]
    scale = seconds / (sum(gaps) + sample.expovariate(1.0))
    random.Random(seed).shuffle(gaps)
    out, t = [], 0.0
    for g in gaps:
        t += g * scale
        out.append(t)
    return out


class Generator:
    def __init__(self, base: str, config: Dict[str, Any], seed: int):
        from minisched_tpu.controlplane.remote import RemoteClient

        self.config = config
        self.seed = seed
        self.maker = load_maker(config)
        self.client = RemoteClient(base)
        self.pods = self.client.pods()
        self.mu = threading.Condition()
        #: pod name -> (node, instant the watch event was read)
        self.bound: Dict[str, Any] = {}
        self.rebinds: List[Any] = []
        #: only a mix that deletes keeps these: the measured pods seen bound
        #: and not yet handed to a deleter, longest-bound first
        self.live: Optional[collections.deque] = None
        self.deleted: List[str] = []
        #: pod name -> [instant its DELETE was sent, instant it was answered]
        self.deleted_at: Dict[str, List[float]] = {}
        self.delete_errors = 0
        #: pod name -> the instant it was due (open) or sent (closed)
        self.due: Dict[str, float] = {}
        self.sent_total = 0
        self.serial = 0
        self.init_prefix = f"s{seed}-init-"
        #: (seconds, instant it began) of the longest create since a run began
        self.slowest_send = (0.0, 0.0)
        self.base = base
        self.conn = self.resp = None
        self.stopping = self.watch_dead = False
        self.watch_thread: Optional[threading.Thread] = None

    # -- the watch -----------------------------------------------------
    def _consume(self) -> None:
        """One line a watch event; a bind is an event whose pod names a
        node.  The lines are read as plain JSON: the program's own watch
        client decodes every event into its object model at half a
        millisecond a pod, which would make this generator, and not the
        stack, the slowest part of a drain."""
        try:
            for raw in self.resp:
                line = raw.strip()
                if not line:
                    continue
                msg = json.loads(line)
                if msg["type"] in ("SYNC", "DELETED"):
                    continue
                node = msg["object"]["spec"]["node_name"]
                if not node:
                    continue
                name = msg["object"]["metadata"]["name"]
                now = time.monotonic()
                with self.mu:
                    seen = self.bound.get(name)
                    if seen is None:
                        self.bound[name] = (node, now)
                        if self.live is not None and not name.startswith(self.init_prefix):
                            self.live.append(name)
                        self.mu.notify_all()
                    elif seen[0] != node:
                        self.rebinds.append([name, seen[0], node])
        except Exception:
            if not self.stopping:
                import traceback

                traceback.print_exc()
        finally:
            with self.mu:
                self.watch_dead = True
                self.mu.notify_all()

    def open_watch(self) -> None:
        import http.client
        import urllib.parse

        u = urllib.parse.urlsplit(self.base)
        self.conn = http.client.HTTPConnection(u.hostname, u.port, timeout=3600)
        self.conn.request("GET", "/api/v1/pods?watch=true")
        self.resp = self.conn.getresponse()
        if self.resp.status != 200:
            raise RuntimeError(f"pod watch: HTTP {self.resp.status}")
        self.watch_thread = threading.Thread(
            target=self._consume, name="bind-watch", daemon=True
        )
        self.watch_thread.start()

    def outstanding(self) -> int:
        return self.sent_total - len(self.bound)

    # -- sending -------------------------------------------------------
    def _reserve(self, count: int) -> int:
        """With ``mu`` held: take the next ``count`` names; they count as
        outstanding from here on, so senders together never pass a target."""
        start = self.serial
        self.serial += count
        self.sent_total += count
        return start

    def _send(
        self,
        kind: str,
        phase: str,
        count: int,
        due: Optional[List[float]] = None,
        start: Optional[int] = None,
        pods_api: Any = None,
    ) -> List[str]:
        if start is None:
            with self.mu:
                start = self._reserve(count)
        pods = self.maker.make_pods(self.config[kind], f"s{self.seed}-{phase}", start, count)
        names = [p.metadata.name for p in pods]
        t_send = time.monotonic()
        with self.mu:
            for i, name in enumerate(names):
                self.due[name] = t_send if due is None else due[i]
        (pods_api or self.pods).create_many(pods, return_objects=False)
        took = time.monotonic() - t_send
        with self.mu:
            if took > self.slowest_send[0]:
                self.slowest_send = (took, t_send)
        return names

    def wait_all_bound(self, seconds: float) -> float:
        """Wait until nothing is outstanding or ``seconds`` pass; returns
        the time it took."""
        t0 = time.monotonic()
        with self.mu:
            while self.outstanding() > 0:
                left = seconds - (time.monotonic() - t0)
                if left <= 0 or self.watch_dead:
                    break
                self.mu.wait(min(left, 0.5))
        return time.monotonic() - t0

    # -- commands ------------------------------------------------------
    def nodes(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        nodes = self.maker.make_nodes(self.config, self.seed)
        for i in range(0, len(nodes), 1000):
            self.client.nodes().create_many(nodes[i : i + 1000], return_objects=False)
        return {"nodes": len(nodes)}

    def init(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        self.open_watch()
        count = self.config["init_pods"]["count"]
        t0 = time.monotonic()
        for i in range(0, count, 1000):
            self._send("init_pods", "init", min(1000, count - i))
        self.wait_all_bound(cmd["deadline_s"])
        return {
            "sent": count,
            "unbound": self.outstanding(),
            "seconds": time.monotonic() - t0,
        }

    def burst(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        t0 = time.monotonic()
        count = cmd["count"]
        chunk = cmd.get("chunk") or count
        for i in range(0, count, chunk):
            self._send("measured_pods", "warm", min(chunk, count - i))
        self.wait_all_bound(cmd["deadline_s"])
        return {"unbound": self.outstanding(), "seconds": time.monotonic() - t0}

    def _closed_loop(self, traffic: Dict[str, Any], phase: str, t_end: float, names: List[str]) -> None:
        """``senders`` threads keep ``outstanding`` pods created and not yet
        seen bound.  A chunk counts as outstanding from the moment a sender
        takes it, so the threads together never pass the target.  With
        ``live_target`` set, ``deleters`` threads hold the measured pods
        seen bound to that many, and the cap counts the pods live."""
        from minisched_tpu.controlplane.remote import RemoteClient

        target, chunk = traffic["outstanding"], traffic["chunk"]
        live_target = traffic.get("live_target")
        cap = self.config["live_pod_cap"]
        errors: List[BaseException] = []

        def sender() -> None:
            client = RemoteClient(self.base)
            pods_api = client.pods()
            try:
                while time.monotonic() < t_end and not errors:
                    with self.mu:
                        need = min(chunk, cap - self.sent_total + len(self.deleted))
                        if need <= 0 and self.outstanding() == 0 and not live_target:
                            return  # the cluster is full: the window ends here
                        if need <= 0 or target - self.outstanding() < need:
                            self.mu.wait(0.005)
                            continue
                        start = self._reserve(need)
                    sent = self._send("measured_pods", phase, need, start=start, pods_api=pods_api)
                    with self.mu:
                        names.extend(sent)
            except BaseException as err:  # handed to the thread that answers
                errors.append(err)
            finally:
                client.store.close()

        def deleter() -> None:
            client = RemoteClient(self.base)
            pods_api = client.pods()
            try:
                while time.monotonic() < t_end and not errors:
                    with self.mu:
                        if len(self.live) <= live_target:
                            self.mu.wait(0.005)
                            continue
                        name = self.live.popleft()
                    t_sent = time.monotonic()  # before the request: the pod was there at least until now
                    try:
                        pods_api.delete(name)
                    except KeyError:  # the façade's 404: the pod was not there to delete
                        with self.mu:
                            self.delete_errors += 1
                        continue
                    with self.mu:
                        self.deleted.append(name)
                        self.deleted_at[name] = [t_sent, time.monotonic()]
                        self.mu.notify_all()  # a sender may be waiting under the cap
            except BaseException as err:
                errors.append(err)
            finally:
                client.store.close()

        threads = [
            threading.Thread(target=sender, name=f"sender-{i}", daemon=True)
            for i in range(traffic["senders"])
        ]
        if live_target:
            with self.mu:
                if self.live is None:
                    # everything measured that is bound so far (the fill, the
                    # warm bursts), in the order it was bound; the watch's
                    # reader appends from here on
                    by_instant = sorted(
                        (t, n) for n, (_node, t) in self.bound.items() if not n.startswith(self.init_prefix)
                    )
                    self.live = collections.deque(n for _t, n in by_instant)
            threads += [
                threading.Thread(target=deleter, name=f"deleter-{i}", daemon=True)
                for i in range(traffic["deleters"])
            ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        if errors:
            raise errors[0]

    def run(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        traffic, phase, seconds = cmd["traffic"], cmd["phase"], cmd["seconds"]
        cap = self.config["live_pod_cap"]
        names: List[str] = []
        late: List[float] = []
        t0 = time.monotonic()
        t_end = t0 + seconds
        self.slowest_send = (0.0, t0)
        deleted_before = len(self.deleted)
        if traffic["loop"] == "closed":
            self._closed_loop(traffic, phase, t_end, names)
        elif traffic["loop"] == "open":
            offsets = arrival_offsets(traffic["rate_per_s"], seconds, self.seed)
            i, n = 0, len(offsets)
            while i < n and self.sent_total < cap:
                now = time.monotonic() - t0
                if offsets[i] > now:
                    time.sleep(min(offsets[i] - now, 0.05))
                    continue
                j = i
                while j < n and offsets[j] <= now and j - i < traffic["max_batch"]:
                    j += 1
                due = [t0 + o for o in offsets[i:j]]
                t_send = time.monotonic()
                late += [t_send - d for d in due]
                names += self._send("measured_pods", phase, j - i, due)
                i = j
            time.sleep(max(0.0, t_end - time.monotonic()))
        else:
            raise ValueError(f"traffic loop {traffic['loop']!r}")
        t_close = min(time.monotonic(), t_end) if traffic["loop"] == "closed" else t_end
        with self.mu:
            in_window = sum(
                1 for _node, t in self.bound.values() if t0 <= t < t_close
            )
            outstanding = self.outstanding()
            deleting = {} if self.live is None else {
                "deleted_in_window": len(self.deleted) - deleted_before,
                "live_at_close": len(self.live),
            }
        self.last = {"names": names, "late": late, "t0": t0, "t_close": t_close}
        return {
            "window_s": t_close - t0,
            "sent": len(names),
            "bound_in_window": in_window,
            "outstanding_at_close": outstanding,
            "slowest_create_s": self.slowest_send[0],
            "slowest_create_at_s": self.slowest_send[1] - t0,
            **deleting,
        }

    def grace(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        """Drain-out: outside the timed window, inside the run.  A pod of
        the window still unbound at its end is timed as if it had bound
        then, and counted as failed."""
        took = self.wait_all_bound(cmd["seconds"])
        t_now = time.monotonic()
        last = self.last
        with self.mu:
            unbound = [n for n in last["names"] if n not in self.bound]
            waits = [
                (self.bound[n][1] if n in self.bound else t_now) - self.due[n]
                for n in last["names"]
            ]
            total_unbound = self.outstanding()
        return {
            "grace_s": took,
            "unbound": len(unbound),
            "unbound_any_phase": total_unbound,
            "bind_ms": summary_ms(waits),
            "late_ms": summary_ms(last["late"]),
        }

    def acks(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        with self.mu:
            return {
                "acks": {name: node for name, (node, _t) in self.bound.items()},
                "sent": sorted(self.due),
                "rebinds": self.rebinds,
                "deleted": self.deleted,
                "delete_errors": self.delete_errors,
                "bound_at": {name: t for name, (_node, t) in self.bound.items()},
                "deleted_at": self.deleted_at,
            }

    def stop(self, cmd: Dict[str, Any]) -> Dict[str, Any]:
        self.stopping = True
        if self.conn is not None:
            import socket

            try:  # unblocks the reader, which sits in a read on this socket
                self.conn.sock.shutdown(socket.SHUT_RDWR)
            except OSError:
                pass
            self.watch_thread.join(timeout=10)
            self.conn.close()
        self.client.store.close()
        return {}


def main() -> int:
    gen: Optional[Generator] = None
    out = sys.stdout
    sys.stdout = sys.stderr  # nothing but answers may reach the pipe
    for line in sys.stdin:
        cmd = json.loads(line)
        op = cmd["op"]
        try:
            if op == "hello":
                gen = Generator(cmd["base"], cmd["config"], cmd["seed"])
                reply: Dict[str, Any] = {}
            else:
                reply = getattr(gen, op)(cmd)
            reply["ok"] = True
        except Exception as err:  # the boundary: say it and let run.py end
            import traceback

            traceback.print_exc()
            reply = {"ok": False, "error": f"{type(err).__name__}: {err}"}
        out.write(json.dumps(reply) + "\n")
        out.flush()
        if op == "stop" or not reply["ok"]:
            break
    return 0


if __name__ == "__main__":
    sys.exit(main())
