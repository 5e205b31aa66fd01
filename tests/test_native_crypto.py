"""Batched C++ OpenPGP layer (native/evolu_crypto.cpp) — exact-behavior
parity with the Python oracle (sync/crypto.py + protocol.py), fallback
demotion for every non-canonical shape, and live GnuPG interop in both
directions (reference: packages/evolu/src/sync.worker.ts:50-91,135-173
encrypts with OpenPGP.js v5; gpg is the independent RFC 4880 peer)."""

import pathlib
import shutil
import subprocess

import pytest

from evolu_tpu.core.types import CrdtMessage
from evolu_tpu.sync import native_crypto, protocol
from evolu_tpu.sync.client import decrypt_messages, encrypt_messages
from evolu_tpu.sync.crypto import PgpError, decrypt_symmetric, encrypt_symmetric

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
MN = (FIXTURES / "gpg_password.txt").read_text().strip()

pytestmark = pytest.mark.skipif(
    not native_crypto.native_available(), reason="libevolu_crypto unavailable"
)

# Value matrix: every CrdtValue kind, both int fields (5/int32, 7/int64),
# unicode, NULs (the char*-ABI trap), empty strings, float edge cases.
VALUES = [
    None, "", "x", "héllo ✓ café", "with\x00nul\x00s", "日本語",
    True, False, 0, 1, -1, 2**31 - 1, -(2**31), 2**31, -(2**31) - 1,
    2**63 - 1, -(2**63), 3.14159, -0.0, 1e308, float("inf"), float("-inf"),
]


def _msgs(values=VALUES):
    return tuple(
        CrdtMessage(f"ts{i}", "todo\x00tbl", f"row-{i}", "col\x00umn", v)
        for i, v in enumerate(values)
    )


def _canon(m):
    # bools leave encode_content as varints; both paths decode them as ints
    v = int(m.value) if isinstance(m.value, bool) else m.value
    return CrdtMessage(m.timestamp, m.table, m.row, m.column, v)


def test_native_encrypt_decrypts_via_pure_oracle():
    msgs = _msgs()
    enc = native_crypto.encrypt_batch(msgs, MN)
    assert enc is not None and len(enc) == len(msgs)
    for m, e in zip(msgs, enc):
        assert e.timestamp == m.timestamp
        content = decrypt_symmetric(e.content, MN)
        assert protocol.decode_content(content) == (
            m.table, m.row, m.column,
            int(m.value) if isinstance(m.value, bool) else m.value,
        )
        # and the content bytes are exactly what the Python encoder emits
        assert content == protocol.encode_content(m.table, m.row, m.column, m.value)


def _pure_encrypted(msgs, password):
    return tuple(
        protocol.EncryptedCrdtMessage(
            m.timestamp,
            encrypt_symmetric(
                protocol.encode_content(m.table, m.row, m.column, m.value), password
            ),
        )
        for m in msgs
    )


def test_pure_encrypt_decrypts_via_native_batch():
    msgs = _msgs()
    enc = _pure_encrypted(msgs, MN)
    assert native_crypto.decrypt_batch(enc, MN) == tuple(_canon(m) for m in msgs)


# The S2K hashes salt ‖ password repeated to `count` bytes; the native
# side feeds it from a tile of whole repetitions (ISSUE 32). Password
# lengths on both sides of the tile's 1,024 bytes (a repetition of
# exactly 1,024, longer than any count here, 8 + 1 bytes), counts that
# are a whole number of tiles, a fraction of one, and 65,536.
@pytest.mark.parametrize("count_byte", [0, 1, 15, 16, 96])
@pytest.mark.parametrize("pw_len", [1, 70, 1016, 1500])
def test_native_s2k_matches_pure_for_every_password_length_and_count(
        monkeypatch, pw_len, count_byte):
    from evolu_tpu.sync import crypto

    password = ("correct horse " * 200)[:pw_len]  # ASCII: pw_len bytes
    monkeypatch.setattr(crypto, "_S2K_COUNT_BYTE", count_byte)
    msgs = _msgs(VALUES[:4])
    enc = _pure_encrypted(msgs, password)
    assert all(e.content[14] == count_byte for e in enc)  # the SKESK's count octet

    def demoted(*_a):
        raise AssertionError("the native S2K derived another key: message demoted to the oracle")

    monkeypatch.setattr(native_crypto, "_pure_one", demoted)
    monkeypatch.setattr(native_crypto, "_pure", demoted)
    assert native_crypto.decrypt_batch(enc, password) == tuple(_canon(m) for m in msgs)
    # And the native encrypt (count byte 0) under the same password.
    monkeypatch.undo()
    for m, e in zip(msgs, native_crypto.encrypt_batch(msgs, password)):
        assert decrypt_symmetric(e.content, password) == protocol.encode_content(
            m.table, m.row, m.column, m.value)


def test_pipeline_roundtrip_via_public_entry_points():
    msgs = _msgs()
    assert decrypt_messages(encrypt_messages(msgs, MN), MN) == tuple(
        _canon(m) for m in msgs
    )


def test_unencodable_values_fall_back_to_oracle_errors():
    # bytes can never travel the wire; int beyond int64 exceeds the codec
    for bad in (b"raw", 2**64):
        msgs = (CrdtMessage("t", "todo", "r", "c", bad),)
        assert native_crypto.encrypt_batch(msgs, MN) is None
        with pytest.raises(TypeError):
            encrypt_messages(msgs, MN)


def test_nondeterministic_and_distinct_salts():
    msgs = _msgs(["same"] * 3)
    enc = native_crypto.encrypt_batch(msgs, MN)
    cts = [e.content for e in enc]
    assert len(set(cts)) == 3  # fresh salt + prefix per message
    salts = {ct[6:14] for ct in cts}  # SKESK v4 salt offset
    assert len(salts) == 3


def test_wrong_password_raises_identically():
    enc = native_crypto.encrypt_batch(_msgs(["v"]), MN)
    with pytest.raises(PgpError, match="wrong password"):
        native_crypto.decrypt_batch(enc, "not the password")
    with pytest.raises(PgpError, match="wrong password"):
        decrypt_messages(enc, "not the password")


def test_mdc_tamper_detected_through_batch():
    enc = native_crypto.encrypt_batch(_msgs(["v"]), MN)
    ct = bytearray(enc[0].content)
    ct[-1] ^= 0x01  # inside the MDC trailer
    bad = (protocol.EncryptedCrdtMessage("t", bytes(ct)),)
    with pytest.raises(PgpError):
        native_crypto.decrypt_batch(bad, MN)


def test_malformed_first_failure_order_matches_pure():
    """Mixed batch: [good, malformed, good] must raise the malformed
    message's error (not return partial results), like the pure loop."""
    good = native_crypto.encrypt_batch(_msgs(["a", "b"]), MN)
    batch = (good[0], protocol.EncryptedCrdtMessage("t", b"\x00garbage"), good[1])
    with pytest.raises(PgpError):
        native_crypto.decrypt_batch(batch, MN)


def test_gpg_golden_ciphertexts_via_batch():
    """The frozen gpg fixtures: 'none' decodes on the canonical fast
    path; zip/zlib are Compressed Data → demoted to the oracle, same
    result either way."""
    plaintext = (FIXTURES / "gpg_plaintext.bin").read_bytes()
    expected = protocol.decode_content(plaintext)
    for name in (
        "gpg_aes256_s2k1024_none.pgp",
        "gpg_aes256_s2k1024_zip.pgp",
        "gpg_aes256_s2k1024_zlib.pgp",
    ):
        enc = (protocol.EncryptedCrdtMessage("t", (FIXTURES / name).read_bytes()),)
        (out,) = native_crypto.decrypt_batch(enc, MN)
        assert (out.table, out.row, out.column, out.value) == expected, name


@pytest.mark.skipif(shutil.which("gpg") is None, reason="gpg not on PATH")
def test_gpg_decrypts_native_ciphertext(tmp_path):
    """Live interop: a ciphertext the C++ path produced must decrypt
    with GnuPG to the exact content bytes."""
    msgs = (CrdtMessage("t", "todo", "r-1", "title", "Buy milk ✓ café"),)
    enc = native_crypto.encrypt_batch(msgs, MN)
    ct_file = tmp_path / "msg.pgp"
    ct_file.write_bytes(enc[0].content)
    res = subprocess.run(
        [
            "gpg", "--homedir", str(tmp_path), "--batch",
            "--pinentry-mode", "loopback", "--passphrase", MN,
            "--decrypt", str(ct_file),
        ],
        capture_output=True,
        check=True,
    )
    assert res.stdout == protocol.encode_content("todo", "r-1", "title", "Buy milk ✓ café")


@pytest.mark.skipif(shutil.which("gpg") is None, reason="gpg not on PATH")
def test_native_decrypts_fresh_gpg_ciphertext(tmp_path):
    """Live interop the other way: encrypt with gpg NOW (fresh salt,
    its own packet writer) and decrypt through the batch."""
    content = protocol.encode_content("todo", "r-2", "done", 1)
    src = tmp_path / "plain.bin"
    src.write_bytes(content)
    out = tmp_path / "out.pgp"
    subprocess.run(
        [
            "gpg", "--homedir", str(tmp_path), "--batch", "--yes",
            "--pinentry-mode", "loopback", "--passphrase", MN,
            "--symmetric", "--cipher-algo", "AES256",
            "--s2k-mode", "3", "--s2k-digest-algo", "SHA256",
            "--s2k-count", "1024", "--compress-algo", "none",
            "--output", str(out), str(src),
        ],
        capture_output=True,
        check=True,
    )
    enc = (protocol.EncryptedCrdtMessage("t", out.read_bytes()),)
    (msg,) = native_crypto.decrypt_batch(enc, MN)
    assert (msg.table, msg.row, msg.column, msg.value) == ("todo", "r-2", "done", 1)


def _oracle_vs_native(content: bytes):
    """Encrypt crafted content bytes with the pure path, then compare
    the native batch outcome against the oracle outcome-for-outcome."""
    ct = encrypt_symmetric(content, MN)
    enc = (protocol.EncryptedCrdtMessage("t", ct),)
    try:
        oracle = protocol.decode_content(decrypt_symmetric(ct, MN))
    except (PgpError, ValueError) as e:
        oracle = type(e)
    try:
        (m,) = native_crypto.decrypt_batch(enc, MN)
        got = (m.table, m.row, m.column, m.value)
    except (PgpError, ValueError) as e:
        got = type(e)
    assert got == oracle, f"{content!r}: oracle {oracle!r} vs native {got!r}"


def test_ten_byte_varint_overflow_matches_oracle():
    """The Python varint reader keeps UNBOUNDED precision on the 10th
    byte; a mod-2^64 wrap in C++ would remap overflowed field keys to
    real fields, decode overflowed lengths 'successfully', and bend
    field-7 ints (r4 review finding). All such shapes must demote to
    the oracle."""
    base = protocol.encode_content("todo", "r", "c", None)
    ten = lambda last: bytes([0x80] * 9 + [last])  # 9 continuations + final
    crafted = [
        # field 7 varint whose 10th byte carries bits >= 2^64: the
        # oracle decodes a huge positive Python int
        base + bytes([7 << 3]) + ten(0x05),
        # overflowed FIELD KEY (2^64 + tag(1, wt2) = 0x8A 0x80×8 0x02):
        # a huge unknown field to the oracle (payload skipped), would
        # wrap to field 1 = table in C++
        base + bytes([0x8A] + [0x80] * 8 + [0x02]) + bytes([3]) + b"zzz",
        # overflowed wt2 LENGTH (2^64 + 3): oracle raises truncated
        bytes([(1 << 3) | 2]) + ten(0x03) + b"abc" + base,
        # the maximal legitimate 10-byte varint (bit 63 set, 10th byte
        # 0x01): both paths must decode int64 min
        base + bytes([7 << 3]) + bytes([0x80] * 9 + [0x01]),
        # 10th byte with continuation set: oracle raises varint too long
        base + bytes([7 << 3]) + bytes([0x80] * 10 + [0x00]),
    ]
    for content in crafted:
        _oracle_vs_native(content)


def test_fused_push_request_matches_pure_encoder():
    """`encode_push_request` must be structurally byte-compatible with
    `protocol.encode_sync_request`: same field order, a decodable
    messages stream whose ciphertexts the pure oracle decrypts to the
    exact contents, and identical trailing scalar fields."""
    msgs = _msgs()
    body = native_crypto.encode_push_request(msgs, MN, "user-1", "f" * 16, '{"h":1}')
    assert body is not None
    req = protocol.decode_sync_request(body)
    assert (req.user_id, req.node_id, req.merkle_tree) == ("user-1", "f" * 16, '{"h":1}')
    assert len(req.messages) == len(msgs)
    for m, e in zip(msgs, req.messages):
        assert e.timestamp == m.timestamp
        assert protocol.decode_content(decrypt_symmetric(e.content, MN)) == (
            m.table, m.row, m.column,
            int(m.value) if isinstance(m.value, bool) else m.value,
        )
    tail = protocol.encode_sync_request(
        protocol.SyncRequest((), "user-1", "f" * 16, '{"h":1}')
    )
    assert body.endswith(tail)
    # Unencodable values route the WHOLE batch to the pure path.
    assert native_crypto.encode_push_request(
        (CrdtMessage("t", "todo", "r", "c", b"raw"),), MN, "u", "n", "{}"
    ) is None


def test_fused_response_decode_parity_and_fallbacks():
    """`decrypt_response` == decode_sync_response + decrypt_messages
    for canonical rows, demotes non-canonical ciphertexts per message
    (a gpg ZIP-compressed fixture decrypts identically through the
    oracle at its position), falls back wholesale on non-canonical
    wire, and raises the oracle's errors."""
    msgs = _msgs()
    enc = list(native_crypto.encrypt_batch(msgs, MN))
    # Splice in a compressed gpg ciphertext (canonical-path reject).
    gpg_ct = (FIXTURES / "gpg_aes256_s2k1024_zip.pgp").read_bytes()
    enc.insert(3, protocol.EncryptedCrdtMessage("ts-gpg", gpg_ct))
    resp_bytes = protocol.encode_sync_response(
        protocol.SyncResponse(tuple(enc), '{"t":2}')
    )
    fused = native_crypto.decrypt_response(resp_bytes, MN)
    assert fused is not None
    got_msgs, got_tree = fused
    resp = protocol.decode_sync_response(resp_bytes)
    from evolu_tpu.sync.client import decrypt_messages

    assert got_msgs == decrypt_messages(resp.messages, MN)
    assert got_tree == '{"t":2}'

    with pytest.raises(PgpError, match="wrong password"):
        native_crypto.decrypt_response(resp_bytes, "nope")
    # Garbage / non-canonical wire: wholesale fallback (None), so the
    # pure decoder owns the ValueError surface.
    assert native_crypto.decrypt_response(b"\x07garbage", MN) is None
    # Truncated by one byte (the tree field's length no longer fits):
    # also wholesale fallback, mirroring the pure decoder's ValueError.
    assert native_crypto.decrypt_response(resp_bytes[:-1], MN) is None
    with pytest.raises(ValueError):
        protocol.decode_sync_response(resp_bytes[:-1])


def test_overflow_length_varints_cannot_escape_bounds():
    """r4 review finding: a 10-byte length varint carrying bit 63 would
    wrap a naive `pos + len > n` check and drive heap over-reads on
    untrusted response bytes (the bit-flip fuzz can't synthesize this
    shape). All such inputs must demote cleanly — fused → None /
    oracle error, never a crash — matching the pure decoder's
    ValueError."""
    huge = bytes([0xFF] * 9 + [0x01])  # varint = 2^64 - 1
    crafted = [
        # SyncResponse: field 1 with a wrapping length, then filler.
        bytes([0x0A]) + huge + b"\x0a\x03abc" * 4,
        # field 2 (merkleTree) with a wrapping length.
        bytes([0x12]) + huge + b"xx",
        # nested: valid message wrapper whose INNER field length wraps.
        bytes([0x0A, 0x0C, 0x0A]) + huge + b"\x00",
    ]
    for data in crafted:
        assert native_crypto.decrypt_response(data, MN) is None, data.hex()
        with pytest.raises(ValueError):
            protocol.decode_sync_response(data)
    # The same shape inside a decrypted CONTENT (decode_content's wt2):
    # oracle raises; the canonical path must demote, not over-read.
    content = protocol.encode_content("t", "r", "c", None) + bytes([0x22]) + huge
    _oracle_vs_native(content)


def test_fuzz_decrypt_response_never_diverges_from_oracle():
    """Random mutations of response bytes: whenever the fused C walker
    accepts the wire (returns non-None), its outcome must equal the
    pure decode+decrypt outcome exactly — value or error type. (A None
    means production runs the pure path, equal by definition.)"""
    import random

    from evolu_tpu.sync.client import decrypt_messages

    rng = random.Random(13)
    base_msgs = _msgs(["a", 7, None])
    enc = native_crypto.encrypt_batch(base_msgs, MN)
    base = protocol.encode_sync_response(protocol.SyncResponse(enc, '{"x":1}'))
    for trial in range(150):
        b = bytearray(base)
        for _ in range(rng.randint(1, 5)):
            op = rng.random()
            if op < 0.6 and b:
                b[rng.randrange(len(b))] ^= 1 << rng.randrange(8)
            elif op < 0.8 and len(b) > 2:
                del b[rng.randrange(len(b))]
            else:
                b.insert(rng.randrange(len(b) + 1), rng.randrange(256))
        data = bytes(b)
        try:
            fused = native_crypto.decrypt_response(data, MN)
        except (PgpError, ValueError) as e:
            fused = type(e)
        if fused is None:
            continue  # production falls back to the pure path
        try:
            resp = protocol.decode_sync_response(data)
            oracle = (decrypt_messages(resp.messages, MN), resp.merkle_tree)
        except (PgpError, ValueError) as e:
            oracle = type(e)
        assert fused == oracle, f"trial {trial}"


def test_fuzz_decrypt_batch_never_diverges_from_oracle():
    """Random mutations of valid ciphertexts: the batch path must
    either produce the oracle's value or raise the oracle's error —
    never a third outcome."""
    import random

    rng = random.Random(7)
    base = native_crypto.encrypt_batch(_msgs(["fuzz-me", 42, None]), MN)
    for trial in range(120):
        ct = bytearray(rng.choice(base).content)
        for _ in range(rng.randint(1, 4)):
            op = rng.random()
            if op < 0.5 and ct:
                ct[rng.randrange(len(ct))] ^= 1 << rng.randrange(8)
            elif op < 0.75 and len(ct) > 2:
                del ct[rng.randrange(len(ct))]
            else:
                ct.insert(rng.randrange(len(ct) + 1), rng.randrange(256))
        enc = (protocol.EncryptedCrdtMessage("t", bytes(ct)),)
        try:
            oracle = protocol.decode_content(decrypt_symmetric(bytes(ct), MN))
        except (PgpError, ValueError) as e:
            oracle = type(e)
        try:
            (m,) = native_crypto.decrypt_batch(enc, MN)
            got = (m.table, m.row, m.column, m.value)
        except (PgpError, ValueError) as e:
            got = type(e)
        assert got == oracle, f"trial {trial}: oracle {oracle!r} vs got {got!r}"
