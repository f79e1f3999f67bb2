"""The client child process. Never imports JAX: the server's process holds
the chip, and the clients must not share its interpreter lock.

    python benchmark/client.py      (started by benchmark/run.py only)

stdin, line 1: the job as JSON
  {"traffic": kind, "traffic_params": {...}, "statements": [...],
   "addr": [host, port], "seed": n, "seconds": s, "timeout_s": s,
   "session_setup": [sql, ...]}
stdout: {"ready": {...}} once warm-up is done; then, after the parent
answers "go" on stdin, the window runs and {"result": {...}} follows.
"""

from __future__ import annotations

import importlib
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))


def main() -> int:
    job = json.loads(sys.stdin.readline())
    traffic = importlib.import_module(f"benchmark.traffic.{job['traffic']}")

    def wait_go(ready: dict) -> None:
        print(json.dumps({"ready": ready}), flush=True)
        line = sys.stdin.readline()
        if line.strip() != "go":
            raise SystemExit(3)

    result = traffic.run(job, wait_go)
    if "jax" in sys.modules:
        raise SystemExit("the client process imported jax")
    print(json.dumps({"result": result}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
