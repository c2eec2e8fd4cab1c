"""Tier-3: the CLI walkthrough — the reference's shell example end-to-end.

Mirrors docs/simple-cli-example.sh: one `sdad` server, a recipient + three
clerks with keys, three keyless participants, additive 3-way sharing of
10-dim mod-433 vectors, expected reveal ``0 2 2 4 4 6 6 8 8 10``.
Runs the real argparse CLI against a live HTTP server.
"""

import pytest

from sda_tpu.crypto import sodium
from sda_tpu.http import SdaHttpServer
from sda_tpu.server import new_jsonfs_server

from sda_tpu.cli.main import main as sda_main

pytestmark = pytest.mark.skipif(not sodium.available(), reason="libsodium not present")


@pytest.fixture
def httpd(tmp_path):
    server = SdaHttpServer(new_jsonfs_server(tmp_path / "server"), bind="127.0.0.1:0")
    server.start_background()
    yield server
    server.shutdown()


def test_simple_cli_walkthrough(httpd, tmp_path, capsys):
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity), *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    # recipient + three clerks, all with encryption keys
    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create")

    # participants don't need encryption keys
    for who in ("part-1", "part-2", "part-3"):
        sda(who, "agent", "create")

    assert sda("recipient", "ping") == '{"running": true}'

    agg_id = sda(
        "recipient", "aggregations", "create", "aggro",
        "--dimension", "10", "--modulus", "433", "--shares", "3",
    )
    sda("recipient", "aggregations", "begin", agg_id)

    sda("part-1", "participate", agg_id, "0", "1", "2", "3", "4", "5", "6", "7", "8", "9")
    sda("part-2", "participate", agg_id, "0", "0", "0", "0", "0", "0", "0", "0", "0", "0")
    sda("part-3", "participate", agg_id, "0", "1", "0", "1", "0", "1", "0", "1", "0", "1")

    sda("recipient", "aggregations", "end", agg_id)

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "clerk", "--once")

    # the reference walkthrough's expected final reveal (README.md)
    assert sda("recipient", "aggregations", "reveal", agg_id) == "0 2 2 4 4 6 6 8 8 10"

    listed = sda("recipient", "aggregations", "list")
    assert agg_id in listed


def test_cli_journal_participate_and_resume(httpd, tmp_path, capsys):
    """`participate --journal` + `sda resume`: a journaled upload reaps
    its entry; a journal entry left by a 'crash' resumes to the SAME
    bytes (deduped server-side), and the round reveals exactly."""
    url = httpd.address

    def sda(identity, *args, rc_expected=0):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                       *args])
        assert rc == rc_expected
        return capsys.readouterr().out.strip()

    sda("recipient", "agent", "create")
    sda("recipient", "agent", "keys", "create")
    for who in ("clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create")
    agg_id = sda(
        "recipient", "aggregations", "create", "journaled",
        "--dimension", "4", "--modulus", "433", "--shares", "3",
    )
    sda("recipient", "aggregations", "begin", agg_id)

    # the happy path: journal written before the upload, reaped after
    sda("part-1", "participate", agg_id, "1", "2", "3", "4", "--journal")
    journal_dir = tmp_path / "agent" / "part-1" / "journal"
    assert list(journal_dir.glob("*.json")) == []  # reaped on confirm
    assert sda("part-1", "resume") == \
        "nothing journaled; all participations confirmed"

    # the crash path: seal + journal WITHOUT uploading (a device that
    # died mid-participate), then `sda resume` re-uploads the same bytes
    from sda_tpu.client import SdaClient
    from sda_tpu.client.journal import ParticipationJournal
    from sda_tpu.cli.main import load_client
    from sda_tpu.protocol import AggregationId

    class _Args:
        identity = str(tmp_path / "agent" / "part-2")
        server = url

    crashed = load_client(_Args)
    crashed.upload_agent()
    sealed = crashed.new_participation([4, 3, 2, 1],
                                       AggregationId(agg_id))
    ParticipationJournal(tmp_path / "agent" / "part-2"
                         / "journal").record(sealed)
    out = sda("part-2", "resume")
    assert out == "resumed 1 of 1 journaled participation(s); 0 still pending"

    sda("recipient", "aggregations", "end", agg_id)
    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal", agg_id) == "5 5 5 5"


def test_cli_shamir_aggregation(httpd, tmp_path, capsys):
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity), *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    sda("recipient", "agent", "create")
    sda("recipient", "agent", "keys", "create")
    for i in range(8):
        sda(f"clerk-{i}", "agent", "create")
        sda(f"clerk-{i}", "agent", "keys", "create")
    agg_id = sda(
        "recipient", "aggregations", "create", "shamir-run",
        "--dimension", "4", "--modulus", "433",
        "--sharing", "shamir", "--shares", "8", "--mask", "chacha",
    )
    sda("recipient", "aggregations", "begin", agg_id)
    sda("p", "participate", agg_id, "1", "2", "3", "4")
    sda("q", "participate", agg_id, "1", "2", "3", "4")
    sda("recipient", "aggregations", "end", agg_id)
    for i in range(8):
        sda(f"clerk-{i}", "clerk", "--once")
    sda("recipient", "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal", agg_id) == "2 4 6 8"


def test_cli_paillier_aggregation(httpd, tmp_path, capsys):
    """--encryption paillier: homomorphic-capable encryption in both slots,
    Paillier keys via `keys create --encryption paillier` (512-bit keys to
    keep the test fast; default is 2048)."""
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity), *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create",
            "--encryption", "paillier", "--paillier-modulus-bits", "512")

    agg_id = sda(
        "recipient", "aggregations", "create", "paillier-run",
        "--dimension", "4", "--modulus", "433", "--shares", "3",
        "--mask", "full", "--encryption", "paillier",
        "--paillier-modulus-bits", "512",
    )
    sda("recipient", "aggregations", "begin", agg_id)
    sda("p", "participate", agg_id, "1", "2", "3", "4")
    sda("q", "participate", agg_id, "10", "20", "30", "40")
    sda("recipient", "aggregations", "end", agg_id)
    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal", agg_id) == "11 22 33 44"


def test_cli_paillier_errors_are_friendly(httpd, tmp_path, capsys):
    """Misconfigured Paillier options exit 1 with an actionable message,
    never a traceback (round-2 advisor findings)."""
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity), *args])
        out = capsys.readouterr()
        return rc, out.out.strip(), out.err

    # keys create with a modulus too small for even one window: friendly error
    rc, _, _ = sda("tiny", "agent", "create")
    assert rc == 0
    rc, _, err = sda("tiny", "agent", "keys", "create",
                     "--encryption", "paillier", "--paillier-modulus-bits", "32")
    assert rc == 1
    assert "error:" in err and "--paillier-modulus-bits" in err

    # aggregations create --encryption paillier over a Sodium primary key:
    # caught at create time with a pointer to the fix, not at participation
    rc, _, _ = sda("mismatched", "agent", "create")
    assert rc == 0
    rc, _, _ = sda("mismatched", "agent", "keys", "create")  # Sodium key
    assert rc == 0
    rc, _, err = sda(
        "mismatched", "aggregations", "create", "bad-run",
        "--dimension", "4", "--modulus", "433", "--shares", "3",
        "--encryption", "paillier", "--paillier-modulus-bits", "512",
    )
    assert rc == 1
    assert "Sodium" in err and "keys create --encryption paillier" in err


def test_sim_cli_clerk_dropout(capsys):
    """`sda-sim --drop-clerks`: the finale reveals exactly from the
    surviving quorum; below-quorum drops fail fast with a clear error."""
    import json

    from sda_tpu.cli import sim

    rc = sim.main([
        "--participants", "8", "--dim", "99", "--clerks", "8",
        "--drop-clerks", "6", "--verify",
    ])
    assert rc == 0
    result = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert result["exact"] is True and result["dropped_clerks"] == [6]
    # every line names the device JAX gave the process (conftest: CPU)
    assert result["platform"] == "cpu" and result["device_kind"] == "cpu"
    assert result["device_count"] == 8

    rc = sim.main([
        "--participants", "8", "--dim", "99", "--clerks", "8",
        "--drop-clerks", "0,1,2,3,4",
    ])
    assert rc == 1
    assert "below the reconstruction threshold" in capsys.readouterr().err


def test_sim_cli_multihost(tmp_path, capsys):
    """`sda-sim --multihost 2` spawns two real worker processes over gRPC
    collectives and prints exactly one JSON result line (worker chatter
    filtered), exact against the distributed plain sum."""
    import json

    from sda_tpu.cli import sim

    rc = sim.main([
        "--participants", "8", "--dim", "24", "--clerks", "8",
        "--multihost", "2", "--devices-per-process", "4", "--verify",
    ])
    assert rc == 0
    out_lines = capsys.readouterr().out.strip().splitlines()
    assert len(out_lines) == 1
    result = json.loads(out_lines[0])
    assert result["mode"].startswith("multihost x2")
    assert result["exact"] is True

    # invalid combination is rejected before any process spawns
    rc = sim.main([
        "--participants", "8", "--dim", "24", "--clerks", "8",
        "--multihost", "3",
    ])
    assert rc == 1


def test_cli_model_participation_fixed_point(httpd, tmp_path, capsys):
    """`participate --model file.npy` + `reveal --fixed-point-bits --mean`:
    the secure mean of float model vectors through the real CLI equals the
    plaintext quantized oracle exactly."""
    import numpy as np

    from sda_tpu.models import FixedPointCodec

    url = httpd.address
    m31 = (1 << 31) - 1

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                       *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create")
    agg_id = sda(
        "recipient", "aggregations", "create", "fedavg",
        "--dimension", "6", "--modulus", str(m31), "--shares", "3",
    )
    sda("recipient", "aggregations", "begin", agg_id)

    rng = np.random.default_rng(0)
    vecs = rng.normal(0, 1, size=(2, 6))
    for i, vec in enumerate(vecs):
        path = tmp_path / f"update{i}.npy"
        np.save(path, vec)
        # NO prior `agent create`: --model as a fresh identity's first
        # command must self-register before its service reads
        sda(f"part-{i}", "participate", agg_id, "--model", str(path),
            "--clip", "4.0")

    sda("recipient", "aggregations", "end", agg_id)

    # a straggler arriving AFTER the snapshot froze the set: counted by
    # the aggregation status but not in the revealed sum — the decoded
    # mean must divide by the snapshot's 2, not the status's 3
    late = tmp_path / "late.npy"
    np.save(late, rng.normal(0, 1, size=6))
    sda("part-late", "participate", agg_id, "--model", str(late),
        "--clip", "4.0")

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "clerk", "--once")

    out = sda("recipient", "aggregations", "reveal", agg_id,
              "--fixed-point-bits", "16", "--mean")
    got = np.array([float(v) for v in out.split()])
    codec = FixedPointCodec(m31, 16, 1024, clip=4.0)
    oracle = np.stack([codec.quantize(v) for v in vecs]).sum(0) \
        / codec.scale / 2
    np.testing.assert_array_equal(got, oracle)

    # --mean without --fixed-point-bits is a usage error, not raw ints
    rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / "recipient"),
                   "aggregations", "reveal", agg_id, "--mean"])
    assert rc == 1
    assert "--fixed-point-bits" in capsys.readouterr().err

    # guard rails: both values and --model, and a wrong-dimension model
    rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / "part-0"),
                   "participate", agg_id, "1", "2",
                   "--model", str(tmp_path / "update0.npy")])
    assert rc == 1
    assert "not both" in capsys.readouterr().err
    bad = tmp_path / "bad.npy"
    np.save(bad, np.zeros(5))
    rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / "part-0"),
                   "participate", agg_id, "--model", str(bad)])
    assert rc == 1
    assert "6" in capsys.readouterr().err


def test_cli_profile_and_chosen_committee(httpd, tmp_path, capsys):
    """`agent profile set/show` and `aggregations begin --clerk ...` — the
    reference README's 'Doing more' aspirations (external-trust profiles,
    recipient-chosen committees) at the CLI surface."""
    import json as _json

    url = httpd.address

    def sda(identity, *args, rc=0):
        got = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                        *args])
        assert got == rc, capsys.readouterr()
        return capsys.readouterr()

    sda("recipient", "agent", "create")
    sda("recipient", "agent", "keys", "create")

    # profile publish + public read-back through REST
    sda("clerk-0", "agent", "create")
    sda("clerk-0", "agent", "profile", "set", "--name", "Clerk Zero",
        "--keybase", "clerk0", "--website", "https://clerk0.example")
    own = _json.loads(sda("clerk-0", "agent", "profile", "show").out)
    assert own["name"] == "Clerk Zero" and own["keybase_id"] == "clerk0"
    clerk0_id = _json.loads(sda("clerk-0", "agent", "show").out)["id"]
    seen = _json.loads(
        sda("recipient", "agent", "profile", "show", clerk0_id).out)
    assert seen["website"] == "https://clerk0.example"

    # recipient-chosen committee: exact clerks, in the chosen order
    clerk_ids = [clerk0_id]
    sda("clerk-0", "agent", "keys", "create")
    for i in range(1, 4):
        sda(f"clerk-{i}", "agent", "create")
        sda(f"clerk-{i}", "agent", "keys", "create")
        clerk_ids.append(
            _json.loads(sda(f"clerk-{i}", "agent", "show").out)["id"])

    agg_id = sda("recipient", "aggregations", "create", "chosen",
                 "--dimension", "4", "--modulus", "433",
                 "--shares", "3").out.strip()
    chosen = [clerk_ids[2], clerk_ids[0], clerk_ids[3]]
    sda("recipient", "aggregations", "begin", agg_id,
        "--clerk", chosen[0], "--clerk", chosen[1], "--clerk", chosen[2])

    from sda_tpu.client import SdaClient
    from sda_tpu.crypto import MemoryKeystore
    from sda_tpu.http import SdaHttpClient
    from sda_tpu.protocol import AggregationId
    from sda_tpu.store import Filebased

    proxy = SdaHttpClient(url, store=Filebased(tmp_path / "probe"))
    ks = MemoryKeystore()
    probe = SdaClient(SdaClient.new_agent(ks), ks, proxy)
    probe.upload_agent()
    committee = proxy.get_committee(probe.agent, AggregationId(agg_id))
    assert [str(c) for c, _ in committee.clerks_and_keys] == chosen

    # full round still reveals exactly with the chosen committee
    sda("p1", "participate", agg_id, "1", "2", "3", "4")
    sda("p2", "participate", agg_id, "4", "3", "2", "1")
    sda("recipient", "aggregations", "end", agg_id)
    for i in range(4):
        sda(f"clerk-{i}", "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal",
               agg_id).out.strip() == "5 5 5 5"

    # guard rails: wrong count, keyless clerk
    err = sda("recipient", "aggregations", "begin", agg_id,
              "--clerk", chosen[0], rc=1).err
    assert "exactly 3" in err
    sda("nokey", "agent", "create")
    nokey_id = _json.loads(sda("nokey", "agent", "show").out)["id"]
    err = sda("recipient", "aggregations", "begin", agg_id,
              "--clerk", chosen[0], "--clerk", chosen[1],
              "--clerk", nokey_id, rc=1).err
    assert "not a committee candidate" in err


def test_cli_embedded_participation(httpd, tmp_path, capsys):
    """`participate --embedded`: the C-core participation over real REST,
    mixed with a Python participant — the walkthrough sum must still be
    exact (the embeddable-client path, reference README.md:196-204)."""
    from sda_tpu import native
    from sda_tpu.crypto import sodium

    if not (sodium.available() and native.available()):
        pytest.skip("libsodium or native library not present")
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                       *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create")
    for who in ("part-1", "part-2"):
        sda(who, "agent", "create")

    agg_id = sda(
        "recipient", "aggregations", "create", "embedded-round",
        "--dimension", "4", "--modulus", "433", "--shares", "3",
        "--mask", "chacha",
    )
    sda("recipient", "aggregations", "begin", agg_id)
    sda("part-1", "participate", agg_id, "1", "2", "3", "4", "--embedded")
    sda("part-2", "participate", agg_id, "10", "20", "30", "40")
    sda("recipient", "aggregations", "end", agg_id)
    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal", agg_id) == "11 22 33 44"


def test_cli_embedded_shamir_participation(httpd, tmp_path, capsys):
    """`participate --embedded` over a packed-Shamir committee via REST."""
    from sda_tpu import native
    from sda_tpu.crypto import sodium

    if not (sodium.available() and native.available()):
        pytest.skip("libsodium or native library not present")
    url = httpd.address

    def sda(identity, *args):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                       *args])
        assert rc == 0
        return capsys.readouterr().out.strip()

    for who in ("recipient",) + tuple(f"clerk-{i}" for i in range(8)):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create")
    sda("part", "agent", "create")
    agg_id = sda(
        "recipient", "aggregations", "create", "shamir-embedded",
        "--dimension", "4", "--modulus", "433",
        "--sharing", "shamir", "--shares", "8",
    )
    sda("recipient", "aggregations", "begin", agg_id)
    sda("part", "participate", agg_id, "1", "2", "3", "4", "--embedded")
    sda("recipient", "aggregations", "end", agg_id)
    for who in ("recipient",) + tuple(f"clerk-{i}" for i in range(8)):
        sda(who, "clerk", "--once")
    assert sda("recipient", "aggregations", "reveal", agg_id) == "1 2 3 4"


def test_cli_embedded_rejects_paillier_cleanly(httpd, tmp_path, capsys):
    """`participate --embedded` on a Paillier aggregation: clear error,
    exit 1, no traceback (the embedded core is Sodium-only)."""
    from sda_tpu import native
    from sda_tpu.crypto import sodium

    if not (sodium.available() and native.available()):
        pytest.skip("libsodium or native library not present")
    url = httpd.address

    def sda(identity, *args, expect_rc=0):
        rc = sda_main(["-s", url, "-i", str(tmp_path / "agent" / identity),
                       *args])
        assert rc == expect_rc
        return capsys.readouterr()

    for who in ("recipient", "clerk-1", "clerk-2", "clerk-3"):
        sda(who, "agent", "create")
        sda(who, "agent", "keys", "create",
            "--encryption", "paillier", "--paillier-modulus-bits", "512")
    sda("part", "agent", "create")
    agg_id = sda(
        "recipient", "aggregations", "create", "paillier-round",
        "--dimension", "4", "--modulus", "433", "--shares", "3",
        "--encryption", "paillier", "--paillier-modulus-bits", "512",
    ).out.strip()
    sda("recipient", "aggregations", "begin", agg_id)
    captured = sda("part", "participate", agg_id, "1", "2", "3", "4",
                   "--embedded", expect_rc=1)
    assert "embedded participation failed" in captured.err
    assert "Sodium" in captured.err
