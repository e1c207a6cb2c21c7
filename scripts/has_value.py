"""Exit 0 iff the file's last JSON line carries a non-null "value".

The one shared gate for bench output (scripts/chip_session.sh — its sole
caller since the adaptive follow-on stage was folded into the session's
flagship-noadaptive arm): a landed measurement is a last JSON line with a
non-null value — keeping the contract in one place stops orchestration
scripts from drifting.
"""

import json
import sys


def main(path: str) -> int:
    try:
        with open(path) as f:
            lines = [l for l in f if l.strip().startswith("{")]
        entry = json.loads(lines[-1]) if lines else {}
        # A line marked stale (older bench versions echoed the last
        # durable-log number for a lost run) is NOT a landed measurement.
        return 0 if entry.get("value") is not None and not entry.get("stale") else 1
    except Exception:  # noqa: BLE001 — any unreadable file is "no value"
        return 1


if __name__ == "__main__":
    sys.exit(main(sys.argv[1]))
