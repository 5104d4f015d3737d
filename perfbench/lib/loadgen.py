"""The load generator: one child process that never touches the chip.

Started by the serving runner with a plan file; speaks to the node through
its two doors only (the WS ``run-generation`` event through the client
SDK, and ``POST /data-centric/run-generation``). Protocol with the parent,
over the child's stdin/stdout: the child connects its workers and prints
``CONNECTED`` (the parent warms the engine meanwhile); on ``PROBE`` it sends
one request through each door and prints ``READY``; the parent answers ``GO
<epoch seconds>``; the child sends the schedule from that instant and
writes every request's times and answer to the plan's ``out`` file.

Open loop: a dispatcher hands each request to its door's workers when it
is due, whether or not earlier ones have answered, and a request is timed
from when it was due. Closed loop: each caller sends its next request when
the last one answers, until the window closes; a caller that finds the
list spent before then says so (``spent``), and the runner fails the run.

A request's named fields (``lib/traffic.py``) go out beside ``n_new``, and
whatever an answer holds beside its tokens is kept under the row's
``answer``: the generator reads neither.
"""

from __future__ import annotations

import base64
import json
import queue
import sys
import threading
import time
from collections.abc import Mapping
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[2]))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from lib import traffic as T  # noqa: E402


def _plain(value):
    """An array as lists, anything else as it is: what JSON can hold."""
    return value.tolist() if hasattr(value, "tolist") else value


class Door:
    """One connection through one door; ``send`` returns the answer's
    fields, ``tokens`` among them."""

    def __init__(self, kind: str, url: str, model_id: str, timeout: float,
                 token: str | None = None) -> None:
        from pygrid_tpu.client import DataCentricFLClient

        self.kind, self.url, self.model_id = kind, url, model_id
        self.timeout = timeout
        if kind == "ws":
            self.client = DataCentricFLClient(url, timeout=timeout)
            self.token = self.client._auth_token
        else:
            import requests

            self.client = requests.Session()
            self.token = token

    def send(self, prompt, n_new: int, **fields) -> dict:
        if self.kind == "ws":
            got = self.client.run_remote_generation(
                self.model_id, prompt, n_new=n_new, **fields
            )
            # an array is the tokens; a mapping names them among its fields
            got = got if isinstance(got, Mapping) else {"tokens": got}
            return {k: _plain(v) for k, v in got.items()}
        from pygrid_tpu.serde import serialize

        resp = self.client.post(
            self.url + "/data-centric/run-generation",
            headers={"token": self.token},
            json={
                "model_id": self.model_id,
                "data": base64.b64encode(serialize(prompt)).decode(),
                "n_new": n_new,
                "temperature": 0.0,
                **fields,
            },
            timeout=self.timeout,
        )
        if resp.status_code != 200:
            raise RuntimeError(f"HTTP {resp.status_code}: {resp.text[:200]}")
        body = resp.json()
        body.pop("success", None)
        return body

    def close(self) -> None:
        self.client.close()


def _serve(door: Door, req: dict, prompts: dict, t_go: float, results: list) -> None:
    """Send one request, record its times (seconds from ``t_go``)."""
    row = {
        "i": req["i"], "door": door.kind, "prompt_len": req["prompt_len"],
        "n_new": req["n_new"], "due": req["due"], "counted": req["counted"],
        "queued": req.get("queued"), "fields": req["fields"],
    }
    row["sent"] = time.time() - t_go
    try:
        answer = door.send(prompts[req["i"]], req["n_new"], **req["fields"])
        row["tokens"] = answer.pop("tokens")
        if answer:
            row["answer"] = answer
        row["ok"] = True
    except Exception as err:  # noqa: BLE001 — a failed request is a result
        row["ok"], row["error"] = False, f"{type(err).__name__}: {err}"[:300]
    row["done"] = time.time() - t_go
    results.append(row)


def run_open(plan: dict, doors: dict, built: dict, prompts: dict, t_go: float) -> tuple:
    results: list = []
    queues = {kind: queue.Queue() for kind in doors}

    def worker(door: Door) -> None:
        while True:
            req = queues[door.kind].get()
            if req is None:
                return
            _serve(door, req, prompts, t_go, results)

    threads = [
        threading.Thread(target=worker, args=(d,), daemon=True)
        for ds in doors.values() for d in ds
    ]
    for t in threads:
        t.start()
    for req in built["requests"]:
        wait = t_go + req["due"] - time.time()
        if wait > 0:
            time.sleep(wait)
        req["queued"] = time.time() - t_go
        queues[req["door"]].put(req)
    for kind, ds in doors.items():
        for _ in ds:
            queues[kind].put(None)
    deadline = time.time() + plan["traffic"]["drain_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.time()))
    return results, {}


def run_closed(plan: dict, doors: dict, built: dict, prompts: dict, t_go: float) -> tuple:
    """The results, and how the list held: ``spent`` (a caller wanted a
    request before the window closed and the list had none left: the run
    then offered less load than the cell defines) and ``list_used``, the
    share of the list that was sent."""
    results: list = []
    lock = threading.Lock()
    cursor, spent = [0], [False]
    t_end = t_go + built["lead_in_s"] + plan["seconds"]

    def caller(door: Door) -> None:
        while time.time() < t_end:
            with lock:
                if cursor[0] >= len(built["requests"]):
                    spent[0] = True
                    return
                req = built["requests"][cursor[0]]
                cursor[0] += 1
            _serve(door, req, prompts, t_go, results)

    threads = [
        threading.Thread(target=caller, args=(d,), daemon=True)
        for ds in doors.values() for d in ds
    ]
    for t in threads:
        t.start()
    deadline = t_end + plan["traffic"]["drain_s"]
    for t in threads:
        t.join(max(0.0, deadline - time.time()))
    return results, {
        "spent": spent[0], "list_used": cursor[0] / len(built["requests"]),
    }


def connections(traffic: dict) -> dict:
    """How many connections each door gets: in a closed loop the callers,
    split by the doors' weights; in an open loop enough workers that a due
    request never waits for a free one (the engine holds at most slots +
    queue requests)."""
    total = traffic["callers"] if traffic["loop"] == "closed" else traffic["workers"]
    weight = sum(traffic["doors"].values())
    return {k: max(1, round(total * w / weight)) for k, w in traffic["doors"].items()}


def main(plan_path: str) -> int:
    plan = json.loads(Path(plan_path).read_text())
    traffic = plan["traffic"]
    built = T.build(traffic, plan["seed"], plan["seconds"])
    prompts = {
        r["i"]: T.prompt_tokens(traffic, plan["seed"], r, plan["vocab"])
        for r in built["requests"]
    }
    counts = connections(traffic)
    first = Door("ws", plan["url"], plan["model_id"], plan["timeout"])
    doors: dict = {"ws": [first]}
    for kind, n in counts.items():
        have = len(doors.setdefault(kind, []))
        for _ in range(n - have):
            doors[kind].append(
                Door(kind, plan["url"], plan["model_id"], plan["timeout"], first.token)
            )
    print("CONNECTED", flush=True)
    if sys.stdin.readline().strip() != "PROBE":
        return 2
    # one request through each door before the clock starts: the path
    # answers, and nothing on it is met for the first time in the window
    import numpy as np

    probe = np.ones((1, traffic["prompt_len"].get("lo", 8)), np.int32)
    for ds in doors.values():
        got = ds[0].send(probe, 2)["tokens"]
        assert len(got[0]) == 2, got
    print("READY", flush=True)
    line = sys.stdin.readline().split()
    if len(line) != 2 or line[0] != "GO":
        return 2
    t_go = float(line[1])
    run = run_open if built["loop"] == "open" else run_closed
    results, held = run(plan, doors, built, prompts, t_go)
    for ds in doors.values():
        for d in ds:
            try:
                d.close()
            except Exception:  # noqa: BLE001 — closing a dead socket
                pass
    sent = {r["i"] for r in results}
    if built["loop"] == "open":
        # a request that never came back by the end of the drain is missing
        for req in built["requests"]:
            if req["i"] not in sent:
                results.append({**req, "ok": False, "error": "no answer by the end of the drain"})
    Path(plan["out"]).write_text(json.dumps({
        "lead_in_s": built["lead_in_s"], "loop": built["loop"],
        "connections": counts, "results": sorted(results, key=lambda r: r["i"]),
        **held,
    }))
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
