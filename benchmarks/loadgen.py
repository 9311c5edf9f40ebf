"""The load generator: one producer process, started by run.py, that never
touches the chip (it imports neither jax nor siddhi_tpu).

A producer builds its pool of pre-encoded SXF1 frames from the seed, then
sends them, one connection per frame (the server speaks HTTP/1.0), either

  closed  the next frame leaves the moment the last `200` came back, or
  paced   the producers of all input streams take turns in one round; the
          k-th frame of a producer is due when the events of k rounds, and
          of the producers before it in the round, have been offered at the
          cell's total rate,

rewriting each frame's timestamp block in place so that every event's
timestamp is its global index. It logs for every frame its number, its due
time, its send time, the time the reply came back, the status, the rows
the server said it accepted and the connects it gave up, all on `time.monotonic_ns` (system-wide on
Linux, so the parent's callback reads the same clock).

Protocol with the parent, over the process's own pipes: argv[1] is the spec
(JSON); the producer prints `ready` once its pool is built; the parent
writes `go <port> <t0_ns> <t_end_ns>`; the producer prints its log as one
JSON line when the window has closed and its last reply is in.
"""

from __future__ import annotations

import http.client
import json
import sys
import time

import registry
import sxf1

# ----------------------------------------------- frame numbers, both sides


def frame_number(k: int, index: int, warm: int, producers: int) -> int:
    """Global number of the k-th measured frame of the producer with global
    index `index` (the producers of all input streams are numbered in one
    list). Numbers below `warm` are the parent's warm-up frames.
    `record.Events.source` is the way back."""
    return warm + k * producers + index


def paced_due_ns(k: int, t0_ns: int, rate: float, round_events: int,
                 lead_events: int) -> int:
    """One round is one frame of every producer, `round_events` events in
    all; `lead_events` are those of the producers before this one in the
    round. With P producers of equal frames that is a schedule
    phase-shifted by 1/P of a producer's period: the streams as a whole
    offer `rate` events a second."""
    return t0_ns + int((k * round_events + lead_events) * 1e9 / rate)


# ------------------------------------------------------------ one producer


def build_pool(spec: dict) -> list:
    gen = registry.load_module("generators", spec["generator"])
    return [sxf1.encode_frame(
        gen.wire_columns(gen.columns(spec["params"], spec["seed"],
                                     spec["stream"], spec["producer"], slot),
                         spec["typecodes"], spec["params"]),
        spec["params"]["rows_per_frame"]) for slot in range(spec["pool"])]


CONNECT_PATIENCE_S = 1.0


def connect(host: str, port: int, timeout: float):
    """A connection as a client with a connect timeout opens it: a
    handshake that is not through within CONNECT_PATIENCE_S is given up and
    tried again on a new socket, until `timeout` is used up. Nothing has
    been sent by then, so nothing can arrive twice. Without this, about one
    connection in 10^4 at 60 connections a second stood for 63 s (the
    kernel's own SYN retries), which is one producer out for a whole
    window (PERF.md, PR 22). Returns (connection, tries given up)."""
    given_up = 0
    while True:
        conn = http.client.HTTPConnection(host, port,
                                          timeout=CONNECT_PATIENCE_S)
        try:
            conn.connect()
        except TimeoutError:
            conn.close()
            given_up += 1
            if given_up * CONNECT_PATIENCE_S >= timeout:
                raise
            continue
        conn.sock.settimeout(timeout)
        return conn, given_up


def post(host: str, port: int, path: str, body, timeout: float):
    """One frame, one connection. Returns (status, rows accepted, connects
    given up)."""
    conn, given_up = connect(host, port, timeout)
    try:
        conn.request("POST", path, body=body,
                     headers={"Content-Type": "application/x-siddhi-frames"})
        resp = conn.getresponse()
        reply = resp.read()
        accepted = json.loads(reply).get("accepted", 0) \
            if resp.status == 200 else 0
        return resp.status, accepted, given_up
    finally:
        conn.close()


def produce(spec: dict, pool: list, t0_ns: int, t_end_ns: int) -> dict:
    index, n_prod, warm = spec["index"], spec["producers"], spec["warm"]
    stride = spec["stride"]  # event indexes per frame number
    paced = spec["mode"] == "paced"
    log = {k: [] for k in ("frame", "due_ns", "send_ns", "done_ns", "status",
                           "accepted", "reconnects")}
    time.sleep(max(0.0, (t0_ns - time.monotonic_ns()) / 1e9))
    k = 0
    while True:
        now = time.monotonic_ns()
        due = paced_due_ns(k, t0_ns, spec["rate"], spec["round_events"],
                           spec["lead_events"]) if paced else now
        if due >= t_end_ns:
            break
        if due > now:
            time.sleep((due - now) / 1e9)
        f = frame_number(k, index, warm, n_prod)
        send = time.monotonic_ns()
        if send >= t_end_ns:
            # due inside the window, unsent at its close: attempted, failed
            status, accepted, again, done = -1, 0, 0, send
        else:
            body = pool[k % len(pool)]
            sxf1.patch_timestamps(body, f * stride)
            status, accepted, again = post(
                spec["host"], spec["port"], spec["path"], body,
                spec["post_timeout_s"])
            done = time.monotonic_ns()
        for key, v in zip(log, (f, due, send, done, status, accepted,
                                again)):
            log[key].append(v)
        k += 1
    return log


def main(argv) -> int:
    spec = json.loads(argv[1])
    pool = build_pool(spec)
    print("ready", flush=True)
    word, port, t0_ns, t_end_ns = sys.stdin.readline().split()
    if word != "go":
        return 2
    spec["port"] = int(port)
    log = produce(spec, pool, int(t0_ns), int(t_end_ns))
    print(json.dumps(log), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv))
