"""Wire codec suite: golden frames, roundtrip identity, mutation fuzzing.

Three gates on ``fed.wire``:

  * **Golden fixtures** (tests/fixtures/wire/*.bin, generated ONCE by
    gen_golden.py and checked in): each decodes to the pinned field values
    and array digests, re-encodes byte-identically, and — for
    statistic-bearing frames — reproduces the pinned fused ridge solve.
    Any layout change breaks these loudly; that is the cross-version gate.
  * **Roundtrip identity**: encode -> decode -> encode is the identity on
    bytes, and decode -> encode -> decode the identity on values, over
    random d/m/dtype/ragged-delta grids (seeded; hypothesis variants run
    where the container has it).
  * **Mutation fuzzing**: truncations at every boundary, seeded byte flips,
    length-prefix lies, and alien garbage must ALWAYS produce a typed
    :class:`wire.WireError` — never another exception type, never a frame
    that re-encodes to different bytes (silent mis-decode).
"""
import hashlib
import json
import pathlib
import struct
import zlib

import numpy as np
import pytest

import hypothesis
import hypothesis.strategies as st
from repro.fed import wire

FIXDIR = pathlib.Path(__file__).resolve().parent / "fixtures" / "wire"
EXPECTED = json.loads((FIXDIR / "expected.json").read_text())

_RNG = np.random.default_rng(0xC0DEC)


def _sha(b: bytes) -> str:
    return hashlib.sha256(b).hexdigest()


def _arr_digest(a: np.ndarray) -> str:
    return _sha(np.ascontiguousarray(a, dtype="<f8").tobytes())


def _unpack(tri: np.ndarray, d: int) -> np.ndarray:
    low = np.zeros((d, d))
    low[np.tril_indices(d)] = tri
    return low + np.tril(low, -1).T


def _random_stats_frame(rng, d, dtype, client_id="c"):
    A = rng.standard_normal((2 * d + 1, d))
    return wire.StatsFrame(tri=(A.T @ A)[np.tril_indices(d)],
                           moment=rng.standard_normal(d),
                           count=A.shape[0], dim=d, client_id=client_id,
                           wire_dtype=dtype)


def _frames_equal(a, b) -> bool:
    """Value equality across frame types (arrays compared bit-for-bit)."""
    if type(a) is not type(b):
        return False
    for f in a.__dataclass_fields__:
        va, vb = getattr(a, f), getattr(b, f)
        if isinstance(va, np.ndarray):
            if not (va.dtype == vb.dtype and np.array_equal(va, vb)):
                return False
        elif va != vb:
            return False
    return True


class TestGoldenFrames:
    """The checked-in .bin frames are the layout contract."""

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_decode_matches_pins(self, name):
        data = (FIXDIR / f"{name}.bin").read_bytes()
        exp = EXPECTED[name]
        assert _sha(data) == exp["sha256"], \
            "fixture file corrupted (regenerate ONLY for an intentional " \
            "format break, with a VERSION bump)"
        assert len(data) == exp["nbytes"]
        frame = wire.decode_frame(data)
        assert type(frame).__name__ == exp["frame_type"]
        for field in ("dim", "count", "client_id", "d_orig", "seed", "rhash",
                      "fhash", "lengthscale", "yty",
                      "sigma", "op", "ok", "message", "tenant"):
            if field in exp:
                assert getattr(frame, field) == exp[field], field
        if "offers" in exp:
            assert list(frame.offers) == exp["offers"]
        for field in ("tri", "moment", "A", "b", "w"):
            if f"{field}_sha256" in exp:
                assert _arr_digest(getattr(frame, field)) == \
                    exp[f"{field}_sha256"], \
                    f"decoded {field} drifted: wire layout changed"

    @pytest.mark.parametrize("name", sorted(EXPECTED))
    def test_reencode_byte_identical(self, name):
        data = (FIXDIR / f"{name}.bin").read_bytes()
        assert wire.encode_frame(wire.decode_frame(data)) == data

    @pytest.mark.parametrize("name", [n for n in sorted(EXPECTED)
                                      if "weights_ref" in EXPECTED[n]])
    def test_fused_solve_pinned(self, name):
        """Decoding a golden statistic frame must reproduce the pinned ridge
        solve — the end-to-end meaning of the bytes, not just their shape."""
        exp = EXPECTED[name]
        frame = wire.decode_frame((FIXDIR / f"{name}.bin").read_bytes())
        if hasattr(frame, "tri"):
            G = _unpack(frame.tri.astype("<f8"), frame.dim)
            h = frame.moment.astype("<f8")
        else:
            A = frame.A.astype("<f8")
            G, h = A.T @ A, A.T @ frame.b.astype("<f8")
        w = np.linalg.solve(G + exp["sigma_ref"] * np.eye(G.shape[0]), h)
        np.testing.assert_allclose(w, np.asarray(exp["weights_ref"]),
                                   rtol=1e-12, atol=1e-12)

    def test_golden_covers_every_frame_type_and_dtype(self):
        types = {e["frame_type"] for e in EXPECTED.values()}
        assert types == {"Hello", "StatsFrame", "ProjectedFrame",
                         "RFFFrame", "DeltaRowsFrame", "ControlFrame",
                         "SolveFrame", "WeightsFrame", "AckFrame"}
        stats_dtypes = {e["wire_dtype"] for e in EXPECTED.values()
                        if e["frame_type"] == "StatsFrame"}
        assert stats_dtypes == {"f32", "f64", "bf16"}


class TestRoundtrip:
    @pytest.mark.parametrize("d", [1, 2, 5, 17, 64])
    @pytest.mark.parametrize("dtype", ["f32", "f64", "bf16"])
    def test_stats_roundtrip(self, d, dtype):
        f = _random_stats_frame(np.random.default_rng(d), d, dtype,
                                client_id=f"client-{d}")
        data = wire.encode_frame(f, dtype=dtype)
        assert len(data) == wire.stats_frame_nbytes(
            d, dtype, client_id=f"client-{d}")
        g = wire.decode_frame(data)
        assert (g.dim, g.count, g.client_id, g.wire_dtype) == \
            (d, f.count, f.client_id, dtype)
        # encode(decode(x)) == x: the decoded upcast is exactly invertible.
        assert wire.encode_frame(g) == data
        # decode(encode(decode(x))) == decode(x): stable values.
        assert _frames_equal(wire.decode_frame(wire.encode_frame(g)), g)
        # The upcast target is deterministic per DECODES_TO.
        assert g.tri.dtype == np.dtype(
            {"f32": "<f4", "f64": "<f8", "bf16": "<f4"}[dtype])

    @pytest.mark.parametrize("m,d_orig", [(1, 1), (4, 10), (32, 400)])
    @pytest.mark.parametrize("dtype", ["f32", "bf16"])
    def test_projected_roundtrip(self, m, d_orig, dtype):
        rng = np.random.default_rng(m)
        f = wire.ProjectedFrame(
            tri=_random_stats_frame(rng, m, dtype).tri,
            moment=rng.standard_normal(m), count=9, dim=m, d_orig=d_orig,
            seed=int(rng.integers(2**63)), rhash=int(rng.integers(2**32)),
            client_id="p", wire_dtype=dtype)
        data = wire.encode_frame(f, dtype=dtype)
        assert len(data) == wire.projected_frame_nbytes(m, dtype,
                                                        client_id="p")
        g = wire.decode_frame(data)
        assert (g.dim, g.d_orig, g.seed, g.rhash) == \
            (m, d_orig, f.seed, f.rhash)
        assert wire.encode_frame(g) == data

    @pytest.mark.parametrize("D,d_orig", [(1, 1), (4, 10), (64, 8), (12, 12)])
    @pytest.mark.parametrize("dtype", ["f32", "bf16"])
    def test_rff_roundtrip(self, D, d_orig, dtype):
        """RFF frames roundtrip, including D > d_orig (widening maps) —
        which the sketch layout forbids but this one must carry."""
        rng = np.random.default_rng(D * 131 + d_orig)
        f = wire.RFFFrame(
            tri=_random_stats_frame(rng, D, dtype).tri,
            moment=rng.standard_normal(D), count=9, dim=D, d_orig=d_orig,
            seed=int(rng.integers(2**63)), fhash=int(rng.integers(2**32)),
            lengthscale=float(rng.uniform(0.1, 5.0)),
            client_id="rff", wire_dtype=dtype)
        data = wire.encode_frame(f, dtype=dtype)
        assert len(data) == wire.rff_frame_nbytes(D, dtype, client_id="rff")
        g = wire.decode_frame(data)
        assert (g.dim, g.d_orig, g.seed, g.fhash, g.lengthscale) == \
            (D, d_orig, f.seed, f.fhash, f.lengthscale)
        assert wire.encode_frame(g) == data
        assert _frames_equal(wire.decode_frame(wire.encode_frame(g)), g)

    def test_rff_bad_lengthscale_rejected(self):
        f = _random_stats_frame(np.random.default_rng(0), 4, "f32")
        for bad in (0.0, -1.0, float("nan"), float("inf")):
            with pytest.raises(wire.PayloadError):
                wire.encode_frame(wire.RFFFrame(
                    tri=f.tri, moment=f.moment, count=f.count, dim=4,
                    d_orig=8, seed=1, fhash=2, lengthscale=bad))

    @pytest.mark.parametrize("n,d", [(1, 1), (3, 7), (17, 5), (128, 2)])
    @pytest.mark.parametrize("dtype", ["f32", "f64"])
    def test_delta_roundtrip_ragged(self, n, d, dtype):
        rng = np.random.default_rng(n * 31 + d)
        f = wire.DeltaRowsFrame(A=rng.standard_normal((n, d)),
                                b=rng.standard_normal(n),
                                client_id="rows", wire_dtype=dtype)
        data = wire.encode_frame(f, dtype=dtype)
        assert len(data) == wire.delta_frame_nbytes(n, d, dtype,
                                                    client_id="rows")
        g = wire.decode_frame(data)
        assert g.A.shape == (n, d) and g.b.shape == (n,)
        assert wire.encode_frame(g) == data

    @pytest.mark.parametrize("frame", [
        wire.Hello("t", ("f32", "bf16")),
        wire.ControlFrame("drop", "c9"),
        wire.ControlFrame("restore", ""),
        wire.SolveFrame(1e-3),
        wire.AckFrame(True, "ok"),
        wire.AckFrame(False, "nope — unicode too"),
    ], ids=lambda f: type(f).__name__)
    def test_scalar_frames_roundtrip(self, frame):
        data = wire.encode_frame(frame)
        assert _frames_equal(wire.decode_frame(data), frame)
        assert wire.encode_frame(wire.decode_frame(data)) == data

    def test_bf16_upcast_is_exact_embedding(self):
        """decode(encode(x, bf16)) == exactly the bf16-quantized values in
        f32 — fusing decoded uploads is bit-exact w.r.t. the wire bytes."""
        import ml_dtypes

        f = _random_stats_frame(np.random.default_rng(1), 9, "bf16")
        g = wire.decode_frame(wire.encode_frame(f, dtype="bf16"))
        want = np.asarray(f.tri).astype(ml_dtypes.bfloat16).astype(np.float32)
        np.testing.assert_array_equal(g.tri, want)

    def test_tri_length_consistency_helpers(self):
        from repro.kernels.ops import tri_dim, tri_len

        for d in (1, 2, 3, 10, 100):
            assert tri_dim(tri_len(d)) == d
        with pytest.raises(ValueError):
            tri_dim(4)   # no d has d(d+1)/2 == 4


def _reseal(body: bytes) -> bytes:
    return body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)


def _with_payload(data: bytes, payload: bytes) -> bytes:
    """Re-frame ``data`` around a replacement payload (length + CRC fixed
    up), so tests can craft byte-level MOMENTS-section corruptions that
    still pass the checksum gate."""
    hdr = bytearray(data[:wire.HEADER_BYTES])
    hdr[8:12] = struct.pack("<I", len(payload))
    return _reseal(bytes(hdr) + payload)


class TestMoments:
    """The optional trailing MOMENTS section (yty = sum y^2, one LE f64).

    Presence is inferred from payload length — absence is the byte-identical
    legacy encoding (the pre-moments golden fixtures pin that), and a
    payload with any OTHER surplus still dies as trailing bytes.
    """

    def _frames(self, yty):
        rng = np.random.default_rng(99)
        base = _random_stats_frame(rng, 5, "f32")
        return [
            wire.StatsFrame(tri=base.tri, moment=base.moment, count=11,
                            dim=5, client_id="m", wire_dtype="f32", yty=yty),
            wire.ProjectedFrame(tri=base.tri, moment=base.moment, count=11,
                                dim=5, d_orig=9, seed=3, rhash=77,
                                client_id="m", wire_dtype="f32", yty=yty),
            wire.RFFFrame(tri=base.tri, moment=base.moment, count=11,
                          dim=5, d_orig=9, seed=3, fhash=77, lengthscale=2.0,
                          client_id="m", wire_dtype="f32", yty=yty),
        ]

    def test_moments_roundtrip_exact_f64(self):
        """yty survives the wire exactly — the section is f64 regardless of
        the session dtype, so fusion off decoded uploads stays bit-exact."""
        yty = 1.0 + 2.0 ** -40     # not representable below f64
        nbytes = {wire.StatsFrame: wire.stats_frame_nbytes,
                  wire.ProjectedFrame: wire.projected_frame_nbytes,
                  wire.RFFFrame: wire.rff_frame_nbytes}
        for f in self._frames(yty):
            data = wire.encode_frame(f)
            assert len(data) == nbytes[type(f)](
                5, "f32", client_id="m", moments=True)
            g = wire.decode_frame(data)
            assert g.yty == yty
            assert wire.encode_frame(g) == data

    @pytest.mark.parametrize("dtype", ["f32", "f64", "bf16"])
    def test_moments_dtype_invariant(self, dtype):
        f = _random_stats_frame(np.random.default_rng(7), 4, dtype)
        f = wire.StatsFrame(tri=f.tri, moment=f.moment, count=f.count,
                            dim=4, wire_dtype=dtype, yty=0.1)
        g = wire.decode_frame(wire.encode_frame(f, dtype=dtype))
        assert g.yty == 0.1       # 0.1 quantizes in f32/bf16; f64 doesn't

    def test_absent_moments_is_legacy_bytes(self):
        f = _random_stats_frame(np.random.default_rng(3), 6, "f32")
        assert f.yty is None
        assert len(wire.encode_frame(f)) == wire.stats_frame_nbytes(
            6, "f32", client_id="c") == wire.stats_frame_nbytes(
            6, "f32", client_id="c",
            moments=True) - wire.MOMENTS_SECTION_BYTES

    def test_nonfinite_yty_rejected_on_encode(self):
        for bad in (float("nan"), float("inf"), float("-inf")):
            for f in self._frames(bad):
                with pytest.raises(wire.PayloadError):
                    wire.encode_frame(f)

    def test_nonfinite_yty_rejected_on_decode(self):
        for f in self._frames(4.25):
            data = wire.encode_frame(f)
            payload = data[wire.HEADER_BYTES:-4]
            evil = payload[:-8] + struct.pack("<d", float("nan"))
            with pytest.raises(wire.PayloadError):
                wire.decode_frame(_with_payload(data, evil))

    def test_partial_moments_section_rejected(self):
        """A surplus that is not exactly 8 bytes is trailing garbage, not a
        MOMENTS section — typed rejection, never a silent mis-decode."""
        for f in self._frames(4.25):
            data = wire.encode_frame(f)
            payload = data[wire.HEADER_BYTES:-4]
            for cut in (1, 4, 7):
                with pytest.raises(wire.WireError):
                    wire.decode_frame(_with_payload(data, payload[:-cut]))
            with pytest.raises(wire.WireError):
                wire.decode_frame(_with_payload(data, payload + b"\x00" * 3))

    def test_from_stats_moments_flag(self):
        from repro.core.sufficient_stats import compute_stats

        rng = np.random.default_rng(17)
        A = rng.standard_normal((12, 4)).astype(np.float32)
        b = rng.standard_normal(12).astype(np.float32)
        s = compute_stats(A, b)
        legacy = wire.StatsFrame.from_stats(s, client_id="c")
        carried = wire.StatsFrame.from_stats(s, client_id="c", moments=True)
        assert legacy.yty is None and carried.yty is not None
        # The flag is opt-in: the default upload is the byte-identical
        # pre-moments encoding, one 8-byte section shorter.
        assert len(wire.encode_frame(carried)) == \
            len(wire.encode_frame(legacy)) + wire.MOMENTS_SECTION_BYTES


class TestNegotiation:
    def test_server_prefers_widest(self):
        assert wire.negotiate(("f32", "bf16", "f64")) == "f64"
        assert wire.negotiate(("bf16", "f32")) == "f32"
        assert wire.negotiate(("bf16",)) == "bf16"

    def test_unknown_offers_ignored(self):
        assert wire.negotiate(("f16", "posit8", "f32")) == "f32"

    def test_empty_intersection_is_typed(self):
        with pytest.raises(wire.NegotiationError):
            wire.negotiate(("f16",))
        with pytest.raises(wire.NegotiationError):
            wire.negotiate((), preference=("f32",))

    def test_custom_policy(self):
        assert wire.negotiate(("f64", "bf16"),
                              preference=("bf16", "f32")) == "bf16"

    def test_server_default_matches_container_width(self):
        """With x64 off (this repo's default), the server's policy must not
        prefer f64: the pool would truncate it at admission, so clients
        would pay 2x bytes for nothing."""
        import jax

        from repro.fed import transport

        pref = transport.default_dtype_preference()
        if jax.config.jax_enable_x64:  # pragma: no cover - repo runs x64-off
            assert pref[0] == "f64"
        else:
            assert pref[0] == "f32"
            assert "f64" in pref       # f64-only clients still negotiate

    def test_future_dtype_offer_interoperates(self):
        """A HELLO carrying an offer tag this version does not speak must
        still decode (tag preserved as unknown:N), re-encode byte-identical,
        and negotiate down to a shared dtype."""
        import struct
        import zlib

        good = wire.encode_frame(wire.Hello("t", ("f32",)))
        # Craft offers = [tag 9 (future), tag 1 (f32)] at the byte level.
        tenant = "t".encode()
        payload = struct.pack("<B", 2) + bytes([9, 1]) + \
            struct.pack("<H", len(tenant)) + tenant
        header = good[:8] + struct.pack("<I", len(payload))
        body = header + payload
        data = body + struct.pack("<I", zlib.crc32(body) & 0xFFFFFFFF)
        frame = wire.decode_frame(data)
        assert frame.offers == ("unknown:9", "f32")
        assert wire.encode_frame(frame) == data
        assert wire.negotiate(frame.offers) == "f32"
        # All-unknown offers fail *negotiation* (typed), not decode.
        with pytest.raises(wire.NegotiationError):
            wire.negotiate(("unknown:9",))


def _good_frames():
    rng = np.random.default_rng(7)
    return [
        wire.encode_frame(_random_stats_frame(rng, 6, "f32"), dtype="f32"),
        wire.encode_frame(_random_stats_frame(rng, 4, "bf16"), dtype="bf16"),
        wire.encode_frame(wire.DeltaRowsFrame(
            A=rng.standard_normal((3, 5)), b=rng.standard_normal(3)),
            dtype="f64"),
        wire.encode_frame(wire.Hello("t", ("f64", "f32"))),
        wire.encode_frame(wire.ControlFrame("drop", "x")),
        wire.encode_frame(wire.SolveFrame(0.5)),
        wire.encode_frame(wire.AckFrame(False, "err")),
    ]


def _assert_rejected_or_identical(mutant: bytes, original: bytes):
    """The fuzz contract: typed rejection, or (for mutations the CRC cannot
    see, which single-byte flips never are) a decode identical to the
    original bytes — NEVER a silent mis-decode or a non-Wire exception."""
    try:
        frame = wire.decode_frame(bytes(mutant))
    except wire.WireError:
        return
    assert wire.encode_frame(frame) == original


class TestMutationFuzz:
    @pytest.mark.parametrize("fidx", range(7))
    def test_every_truncation_rejected(self, fidx):
        data = _good_frames()[fidx]
        for cut in range(len(data)):
            with pytest.raises(wire.WireError):
                wire.decode_frame(data[:cut])

    @pytest.mark.parametrize("fidx", range(7))
    def test_seeded_byte_flips_rejected(self, fidx):
        data = _good_frames()[fidx]
        rng = np.random.default_rng(1000 + fidx)
        for _ in range(300):
            mutant = bytearray(data)
            pos = int(rng.integers(len(data)))
            bit = 1 << int(rng.integers(8))
            mutant[pos] ^= bit
            # CRC32 detects every single-bit error; flips that land in the
            # magic/version/length fields fail even earlier. All typed.
            with pytest.raises(wire.WireError):
                wire.decode_frame(bytes(mutant))

    @pytest.mark.parametrize("fidx", range(7))
    def test_multibyte_flips_never_crash(self, fidx):
        data = _good_frames()[fidx]
        rng = np.random.default_rng(2000 + fidx)
        for _ in range(300):
            mutant = bytearray(data)
            for pos in rng.integers(len(data), size=int(rng.integers(2, 9))):
                mutant[int(pos)] = int(rng.integers(256))
            _assert_rejected_or_identical(bytes(mutant), data)

    def test_length_prefix_lies(self):
        data = _good_frames()[0]
        true_plen = len(data) - wire.OVERHEAD_BYTES
        for lie in (0, 1, true_plen - 1, true_plen + 1, true_plen + 1000,
                    2**31 - 1, 2**32 - 1):
            mutant = bytearray(data)
            mutant[8:12] = int(lie).to_bytes(4, "little")
            with pytest.raises(wire.WireError):
                wire.decode_frame(bytes(mutant))
        # An over-cap length must be rejected from the HEADER ALONE (before
        # any allocation) — that is the transport's read-loop guard.
        mutant = bytearray(data[:wire.HEADER_BYTES])
        mutant[8:12] = (wire.MAX_PAYLOAD_BYTES + 1).to_bytes(4, "little")
        with pytest.raises(wire.BadLength):
            wire.frame_total_length(bytes(mutant))

    def test_trailing_garbage_rejected(self):
        data = _good_frames()[0]
        with pytest.raises(wire.BadLength):
            wire.decode_frame(data + b"\x00")
        with pytest.raises(wire.BadLength):
            wire.decode_frame(data + data)

    def test_alien_bytes_rejected(self):
        rng = np.random.default_rng(3)
        for n in (0, 1, 11, 12, 13, 64, 1024):
            blob = rng.integers(256, size=n).astype(np.uint8).tobytes()
            with pytest.raises(wire.WireError):
                wire.decode_frame(blob)
        with pytest.raises(wire.BadMagic):
            wire.decode_frame(b"HTTP/1.1 200 OK\r\n\r\n")

    def test_valid_crc_wrong_dim_rejected(self):
        """A crafted frame whose payload length and CRC are both right but
        whose declared d disagrees with the array bytes: d/len consistency
        must catch what the checksum cannot."""
        data = bytearray(_good_frames()[0])
        # stats payload starts with u32 d at offset HEADER_BYTES
        d = int.from_bytes(data[12:16], "little")
        data[12:16] = (d + 1).to_bytes(4, "little")
        body = bytes(data[:-4])
        crafted = body + (zlib.crc32(body) & 0xFFFFFFFF).to_bytes(4, "little")
        with pytest.raises(wire.PayloadError):
            wire.decode_frame(crafted)

    def test_unknown_frame_type_and_dtype_tags(self):
        data = bytearray(_good_frames()[5])   # solve frame
        for pos, exc in ((5, wire.BadFrameType), (6, wire.BadDtype)):
            mutant = bytearray(data)
            mutant[pos] = 0xEE
            body = bytes(mutant[:-4])
            crafted = body + (zlib.crc32(body) & 0xFFFFFFFF).to_bytes(
                4, "little")
            with pytest.raises(exc):
                wire.decode_frame(crafted)

    def test_future_version_rejected_typed(self):
        data = bytearray(_good_frames()[5])
        data[4] = wire.VERSION + 1
        body = bytes(data[:-4])
        crafted = body + (zlib.crc32(body) & 0xFFFFFFFF).to_bytes(4, "little")
        with pytest.raises(wire.BadVersion):
            wire.decode_frame(crafted)

    def test_nonpositive_sigma_rejected(self):
        for bad in (0.0, -1.0, float("nan"), float("inf")):
            with pytest.raises(wire.PayloadError):
                wire.encode_frame(wire.SolveFrame(bad))


class TestHypothesisFuzz:
    """Property-based variants (skip automatically without hypothesis)."""

    @hypothesis.given(st.binary(max_size=512))
    @hypothesis.settings(max_examples=300, deadline=None)
    def test_arbitrary_bytes_always_typed(self, blob):
        try:
            frame = wire.decode_frame(blob)
        except wire.WireError:
            return
        assert wire.encode_frame(frame) == blob

    @hypothesis.given(st.integers(min_value=1, max_value=48),
                      st.sampled_from(["f32", "f64", "bf16"]),
                      st.integers(min_value=0, max_value=2**31),
                      st.text(max_size=20))
    @hypothesis.settings(max_examples=100, deadline=None)
    def test_stats_roundtrip_property(self, d, dtype, seed, cid):
        f = _random_stats_frame(np.random.default_rng(seed), d, dtype,
                                client_id=cid)
        data = wire.encode_frame(f, dtype=dtype)
        assert wire.encode_frame(wire.decode_frame(data)) == data
