"""Protobuf wire parity (VERDICT r02 item 2): the UNMODIFIED reference
CLI client (/root/reference/service/client.py:29-163) completes the full
ops/SERVICE.md walkthrough against the das_tpu server — create → load →
check → count=(14, 26) → atom/search incl. `af12f10f…` → query — over a
real gRPC channel with the reference's own protobuf messages.

The client subprocess resolves `das_pb2`/`das_pb2_grpc` from our
service_spec (protoc-built from the carried das.proto + hand-written
stubs), `das.*` from the compat shim, and `server` from the reference's
own directory (its module-level `os.environ['COUCHBASE_SETUP_DIR']` is
satisfied by env, not code changes).
"""

import os
import subprocess
import sys
import time

import pytest

from tests.conftest import reference_path

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
HUMAN = "af12f10f9ae2002a1607ba0b47ba8407"
MAMMAL = "bdfe4e7a431f73386f37c6448afe5840"


@pytest.fixture(scope="module")
def das_server():
    reference_path("service", "client.py")  # skip before serving
    from das_tpu.service.server import serve

    server, service = serve(port=0, backend="tensor", block=False)
    yield server.bound_port
    server.stop(0)


def _client(port, *args, timeout=120):
    env = dict(os.environ)
    env.update(
        PYTHONPATH=(
            f"{REPO}/compat:{REPO}:{REPO}/das_tpu/service/service_spec"
        ),
        JAX_PLATFORMS="cpu",
        COUCHBASE_SETUP_DIR="/tmp",
    )
    proc = subprocess.run(
        [sys.executable, reference_path("service", "client.py"),
         "--port", str(port), *args],
        capture_output=True, text=True, timeout=timeout, env=env,
    )
    assert proc.returncode == 0, (proc.stdout + proc.stderr)[-3000:]
    return proc.stdout.strip()


def test_reference_client_full_walkthrough(das_server):
    port = das_server
    token = _client(port, "create", "--new-das-name", "ref-client-das")
    assert len(token) == 20 and token.isalpha()

    out = _client(
        port, "load", "--das-key", token,
        "--url", f"file://{REPO}/data/samples/animals.metta",
    )
    assert "Load request submitted" in out

    for _ in range(60):
        status = _client(port, "check", "--das-key", token)
        if status == "Ready":
            break
        assert "Loading" in status or "Ready" in status, status
        time.sleep(1.0)
    assert status == "Ready"

    assert _client(port, "count", "--das-key", token) == "(14, 26)"

    assert _client(port, "atom", "--das-key", token, "--handle", HUMAN) == HUMAN
    atom_dict = _client(
        port, "atom", "--das-key", token, "--handle", HUMAN,
        "--output-format", "DICT",
    )
    assert "'type': 'Concept'" in atom_dict and "'name': 'human'" in atom_dict

    nodes = _client(
        port, "search_nodes", "--das-key", token,
        "--node-type", "Concept", "--node-name", "human",
    )
    assert nodes == f"['{HUMAN}']"

    links = _client(
        port, "search_links", "--das-key", token,
        "--link-type", "Similarity", "--targets", f"{HUMAN},*",
    )
    # production-DB semantics (redis_mongo_db.py:249-252): the unordered
    # probe hashes SORTED handles and matches stored order, so
    # Similarity [human, *] answers links with human in SECOND position —
    # Similarity(monkey, human) is in, Similarity(human, monkey) is NOT
    # (the reference's own distributed_atom_space_test pins these counts)
    assert "2a8a69c01305563932b957de4b3a9ba6" in links  # Sim(monkey, human)
    assert "16f7e407087bfa0b35b13d13a1aadcae" not in links

    query = _client(
        port, "query", "--das-key", token,
        "--query", "Node n1 Concept human, Link Inheritance n1 $1",
    )
    assert MAMMAL in query

    conj = _client(
        port, "query", "--das-key", token,
        "--query",
        "Node n1 Concept human, Node n2 Concept chimp, "
        "Link Similarity n1 $1, Link Similarity n2 $1, AND",
    )
    assert "1cdffc6b0b89ff41d68bec237481d1e1" in conj  # monkey


def test_reference_client_invalid_key_fails(das_server):
    env_proc = subprocess.run(
        [sys.executable, reference_path("service", "client.py"),
         "--port", str(das_server),
         "count", "--das-key", "nosuchkey"],
        capture_output=True, text=True, timeout=120,
        env={
            **os.environ,
            "PYTHONPATH": f"{REPO}/compat:{REPO}:{REPO}/das_tpu/service/service_spec",
            "JAX_PLATFORMS": "cpu",
            "COUCHBASE_SETUP_DIR": "/tmp",
        },
    )
    # the client asserts response.success — an invalid key must surface
    assert env_proc.returncode != 0
    assert "Invalid DAS key" in env_proc.stderr
