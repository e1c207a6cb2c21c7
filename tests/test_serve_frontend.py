"""JSONL frontend (serve/frontend.py run_server) driven in-process.

The protocol is the product surface: requests in, one response line per
request (any order, correlated by id), malformed lines answered rather
than crashing the server, stdout carrying nothing but protocol lines.
"""

import io
import json

import numpy as np
import pytest

from tpu_bfs.reference.cpu_bfs import bfs_python
from tpu_bfs.serve.frontend import (
    build_arg_parser,
    decode_distances,
    run_server,
)

pytestmark = pytest.mark.serve

GRAPH_SPEC = "random:n=96,m=480,seed=3"


@pytest.fixture(scope="module")
def frontend_registry():
    """One graph load + one warmed engine for every server run in this
    module (tier-1 wall-clock: a fresh build per test costs seconds)."""
    from tpu_bfs.serve import EngineRegistry

    return EngineRegistry(capacity=2)


@pytest.fixture
def _serve(frontend_registry):
    def serve(requests: str, extra_args=()):
        args = build_arg_parser().parse_args(
            [GRAPH_SPEC, "--lanes", "32", "--linger-ms", "1",
             "--statsz-every", "0", *extra_args]
        )
        out, err = io.StringIO(), io.StringIO()
        rc = run_server(args, stdin=io.StringIO(requests), stdout=out,
                        stderr=err, registry=frontend_registry)
        assert rc == 0
        lines = [
            json.loads(l) for l in out.getvalue().splitlines() if l.strip()
        ]
        return lines, err.getvalue()

    return serve


def test_jsonl_round_trip_with_distances(_serve):
    from tpu_bfs.cli import load_graph

    g = load_graph(GRAPH_SPEC)
    reqs = "".join(
        json.dumps({"id": i, "source": s}) + "\n"
        for i, s in enumerate([0, 3, 5])
    )
    lines, err = _serve(reqs)
    assert len(lines) == 3
    by_id = {r["id"]: r for r in lines}
    for i, s in enumerate([0, 3, 5]):
        r = by_id[i]
        assert r["status"] == "ok" and r["source"] == s
        assert r["latency_ms"] >= 0 and r["batch_lanes"] >= 1
        ref, _ = bfs_python(g, s)
        np.testing.assert_array_equal(decode_distances(r["distances_npy"]), ref)
        assert r["levels"] == int(ref.max())  # connected: no INF to mask
    # Final statsz line lands on stderr, never stdout.
    assert "statsz {" in err


def test_no_distances_flag_omits_payload(_serve):
    lines, _ = _serve('{"id": 9, "source": 2}\n', ["--no-distances"])
    (r,) = lines
    assert r["status"] == "ok" and "distances_npy" not in r
    assert r["levels"] >= 1 and r["reached"] >= 1


def test_malformed_and_out_of_range_requests_get_error_lines(_serve):
    reqs = (
        "this is not json\n"
        '[1, 2, 3]\n'
        '{"id": 4}\n'
        '{"id": 5, "source": 100000}\n'
        '{"id": 6, "source": 1}\n'
    )
    lines, _ = _serve(reqs)
    assert len(lines) == 5
    by_id = {r.get("id"): r for r in lines}
    assert by_id[6]["status"] == "ok"
    assert by_id[4]["status"] == "error"  # missing source
    assert by_id[5]["status"] == "error"
    assert "out of range" in by_id[5]["error"]
    bad = [r for r in lines if r.get("id") is None]
    assert len(bad) == 2 and all(r["status"] == "error" for r in bad)


def test_malformed_deadline_is_error_not_crash(_serve):
    # A bogus deadline_ms must answer THAT request with an error and keep
    # serving the rest — one bad client cannot crash the loop.
    reqs = (
        '{"id": 1, "source": 0, "deadline_ms": "soon"}\n'
        '{"id": 2, "source": 1, "deadline_ms": 5000}\n'
    )
    lines, _ = _serve(reqs)
    by_id = {r["id"]: r for r in lines}
    assert by_id[1]["status"] == "error" and "bad request" in by_id[1]["error"]
    assert by_id[2]["status"] == "ok"


def test_auto_ids_when_absent(_serve):
    lines, _ = _serve('{"source": 2}\n{"source": 3}\n')
    assert len(lines) == 2
    assert all(r["status"] == "ok" and r["id"] is not None for r in lines)
    assert lines[0]["id"] != lines[1]["id"]


def test_strict_source_typing(_serve):
    # Hardening: bool/fractional sources are structured errors, never a
    # silent int() coercion (true -> vertex 1, 7.9 -> vertex 7).
    reqs = (
        '{"id": 1, "source": true}\n'
        '{"id": 2, "source": 7.9}\n'
        '{"id": 3, "source": 7.0}\n'
        '{"id": 4, "source": "5"}\n'
    )
    lines, _ = _serve(reqs)
    by_id = {r["id"]: r for r in lines}
    assert by_id[1]["status"] == "error" and "integer" in by_id[1]["error"]
    assert by_id[2]["status"] == "error"
    assert by_id[3]["status"] == "ok"  # integral float: accepted
    assert by_id[4]["status"] == "error"  # strings are not vertex ids


def test_fuzz_line_stream_survives(_serve):
    """Chaos satellite: a hostile request stream — binary garbage, hugely
    nested JSON (RecursionError territory), wrong shapes, bad field types
    — interleaved with valid requests. EVERY line gets exactly one
    response, the valid ones all serve correctly, and the reader loop
    survives to EOF."""
    rng = __import__("numpy").random.default_rng(41)
    garbage = [
        "\x00\x01\x02 not json at all",
        "[" * 4000,  # deep-nesting parser bomb
        '{"source": {"nested": 1}}',
        '{"source": null}',
        '{"id": [1,2], "source": 1e99}',
        '{"source": -9999999999999999999999}',
        '"just a string"',
        "9" * 5000,
        '{"id": 1, "source": 2, "deadline_ms": [1]}',
        '{"id": 2, "source": 2, "want_distances": "yes"}',
    ]
    valid_sources = [0, 1, 2, 3, 5]
    lines_in = []
    valid = 0
    for i in range(60):
        if rng.integers(2):
            lines_in.append(json.dumps(
                {"id": f"ok-{valid}",
                 "source": valid_sources[valid % len(valid_sources)]}
            ))
            valid += 1
        else:
            lines_in.append(garbage[int(rng.integers(len(garbage)))])
    lines, err = _serve("\n".join(lines_in) + "\n")
    assert len(lines) == 60  # one response per line, none dropped
    ok = [r for r in lines if r["status"] == "ok"]
    bad = [r for r in lines if r["status"] == "error"]
    assert len(ok) == valid and len(bad) == 60 - valid
    assert all(str(r["id"]).startswith("ok-") for r in ok)
    assert all("bad request" in r["error"] or "out of range" in r["error"]
               for r in bad)


def test_server_process_exits_zero_after_eof():
    """``python -m tpu_bfs.serve`` drains at EOF and exits 0: the statsz
    thread is joined, not left waking at interpreter exit (that aborted
    every clean shutdown with rc 134, read by the fleet and the handoff
    driver as a crashed replica)."""
    import os
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-m", "tpu_bfs.serve", "random:n=64,m=256,seed=1",
         "--lanes", "32", "--ladder", "off"],
        input='{"id": 1, "source": 0}\n', cwd=repo, capture_output=True,
        text=True, timeout=120,
        env=dict(os.environ, JAX_PLATFORMS="cpu"),
    )
    assert proc.returncode == 0, proc.stderr[-2000:]
    assert json.loads(proc.stdout.splitlines()[0])["status"] == "ok"
