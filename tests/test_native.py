"""Tests for the native C++ data path: parity with pure Python + speed."""

import io
import os
import time

import numpy as np
import pytest

from tensor2robot_tpu.data import example_proto, native, parser, tfrecord

pytestmark = pytest.mark.skipif(
    native.get_native() is None,
    reason="native library unavailable (no toolchain/libjpeg)")


def _jpeg_bytes(h=48, w=64, seed=0, gray=False):
  from PIL import Image
  rng = np.random.default_rng(seed)
  if gray:
    arr = rng.integers(0, 255, (h, w), np.uint8).astype(np.uint8)
  else:
    arr = rng.integers(0, 255, (h, w, 3), np.uint8).astype(np.uint8)
  buf = io.BytesIO()
  Image.fromarray(arr).save(buf, format="JPEG", quality=95)
  return buf.getvalue()


class TestNativeCrcAndFraming:

  def test_crc_parity_random_buffers(self):
    lib = native.get_native()
    rng = np.random.default_rng(0)
    for size in (0, 1, 7, 64, 1000, 65536):
      data = rng.bytes(size)
      assert lib.masked_crc32c(data) == tfrecord.masked_crc32c(data)

  def test_tfrecord_index_round_trip(self, tmp_path):
    lib = native.get_native()
    path = str(tmp_path / "x.tfrecord")
    records = [os.urandom(n) for n in (0, 1, 100, 4096)]
    tfrecord.write_tfrecords(path, records)
    with open(path, "rb") as f:
      buf = f.read()
    offsets, lengths = lib.tfrecord_index(buf)
    assert len(offsets) == len(records)
    for offset, length, expected in zip(offsets, lengths, records):
      assert buf[offset:offset + length] == expected

  def test_read_tfrecords_uses_native_and_matches(self, tmp_path):
    path = str(tmp_path / "y.tfrecord")
    records = [os.urandom(64) for _ in range(10)]
    tfrecord.write_tfrecords(path, records)
    assert list(tfrecord.read_tfrecords(path)) == records

  def test_huge_length_field_rejected_without_crc(self, tmp_path):
    """A corrupt length must not wrap the bounds check (uint64 overflow)
    even with verify_crc=False."""
    import struct
    lib = native.get_native()
    path = str(tmp_path / "w.tfrecord")
    tfrecord.write_tfrecords(path, [b"payload"])
    buf = bytearray(open(path, "rb").read())
    buf[0:8] = struct.pack("<Q", 0xFFFFFFFFFFFFFFF0)
    with pytest.raises(ValueError, match="truncated|Corrupt"):
      lib.tfrecord_index(bytes(buf), verify_crc=False)

  def test_corruption_detected(self, tmp_path):
    lib = native.get_native()
    path = str(tmp_path / "z.tfrecord")
    tfrecord.write_tfrecords(path, [b"hello world" * 10])
    buf = bytearray(open(path, "rb").read())
    buf[20] ^= 0xFF  # flip a payload byte
    with pytest.raises(ValueError, match="CRC|Corrupt"):
      lib.tfrecord_index(bytes(buf))


class TestNativeJpeg:

  def test_decode_matches_pil(self):
    lib = native.get_native()
    from PIL import Image
    data = _jpeg_bytes()
    ours = lib.jpeg_decode(data)
    theirs = np.asarray(Image.open(io.BytesIO(data)))
    assert ours.shape == theirs.shape
    # Different IDCT implementations may differ by a few LSBs.
    assert np.mean(np.abs(ours.astype(int) - theirs.astype(int))) < 2.0

  def test_grayscale(self):
    lib = native.get_native()
    data = _jpeg_bytes(gray=True)
    out = lib.jpeg_decode(data)
    assert out.shape == (48, 64, 1)
    # Force-expand grayscale to RGB.
    out3 = lib.jpeg_decode(data, channels=3)
    assert out3.shape == (48, 64, 3)

  def test_invalid_data_raises(self):
    lib = native.get_native()
    with pytest.raises(ValueError, match="Invalid JPEG"):
      lib.jpeg_decode(b"not a jpeg at all")

  def test_parser_path_uses_native(self):
    data = _jpeg_bytes()
    out = parser.decode_image(data, data_format="jpeg")
    assert out.shape == (48, 64, 3) and out.dtype == np.uint8


class TestNativeSpeed:

  def test_decode_faster_than_pil(self):
    """The native decoder at the flagship's 472x472 frame: same shape and
    dtype as PIL's, pixels equal to the IDCTs' few LSBs. Whether it is
    FASTER is not a question a shared CPU answers (this compared two host
    timings until PR 34, and failed by who shared the core): the
    record-fed cell decides it on the chip's host (ROADMAP B2/D4). The
    test keeps the name the ledger's failed lists know it by."""
    from PIL import Image
    lib = native.get_native()
    data = _jpeg_bytes(h=472, w=472, seed=1)
    ours = lib.jpeg_decode(data)
    theirs = np.asarray(Image.open(io.BytesIO(data)))
    assert ours.shape == theirs.shape == (472, 472, 3)
    assert ours.dtype == theirs.dtype == np.uint8
    difference = np.abs(ours.astype(int) - theirs.astype(int))
    assert difference.mean() < 2.0, difference.mean()


class TestBatchJpegDecode:

  def _jpegs(self, n=8, size=32, seed=0):
    import io
    from PIL import Image
    rng = np.random.default_rng(seed)
    images, arrays = [], []
    for _ in range(n):
      arr = rng.integers(0, 255, (size, size, 3), np.uint8)
      buf = io.BytesIO()
      Image.fromarray(arr).save(buf, "JPEG", quality=95)
      images.append(buf.getvalue())
      arrays.append(arr)
    return images, arrays

  def test_batch_matches_single(self):
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    images, _ = self._jpegs(n=8)
    out, statuses = lib.jpeg_decode_batch(images, 32, 32, 3)
    assert (statuses == 0).all()
    for i, image in enumerate(images):
      np.testing.assert_array_equal(out[i], lib.jpeg_decode(image))

  def test_per_image_failures_isolated(self):
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    images, _ = self._jpegs(n=3)
    bad = [images[0], b"corrupt bytes", images[2]]
    out, statuses = lib.jpeg_decode_batch(bad, 32, 32, 3)
    assert statuses[0] == 0 and statuses[2] == 0
    assert statuses[1] == -1
    assert (out[1] == 0).all()  # failed slot left zeroed
    np.testing.assert_array_equal(out[0], lib.jpeg_decode(images[0]))

  def test_truncated_jpeg_slot_zeroed(self):
    # Valid header + cut-off entropy data: libjpeg aborts mid-scanline
    # after writing partial rows; the slot must still come back zeroed.
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    images, _ = self._jpegs(n=1, size=64)
    truncated = images[0][: len(images[0]) // 2]
    out, statuses = lib.jpeg_decode_batch([truncated], 64, 64, 3)
    assert statuses[0] != 0
    assert (out[0] == 0).all()

  def test_dimension_mismatch_status(self):
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    images, _ = self._jpegs(n=2, size=32)
    out, statuses = lib.jpeg_decode_batch(images, 64, 64, 3)
    assert (statuses == -2).all()
    # The output buffer is np.empty (not pre-zeroed) since 2026-07-31;
    # the zeroed-failed-slot contract must hold for the -2 path too —
    # it is enforced by a memset inside the C++ worker.
    assert (out == 0).all()

  def test_empty_batch(self):
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    out, statuses = lib.jpeg_decode_batch([], 32, 32, 3)
    assert out.shape == (0, 32, 32, 3) and statuses.shape == (0,)

  def test_grayscale_batch(self):
    lib = native.get_native()
    if lib is None:
      pytest.skip("native library unavailable")
    images, _ = self._jpegs(n=4)
    out, statuses = lib.jpeg_decode_batch(images, 32, 32, channels=1)
    assert (statuses == 0).all()
    assert out.shape == (4, 32, 32, 1)


class TestNativeExampleParse:

  def _records(self, n=8, seed=0, image=False, raw_bytes=False):
    rng = np.random.default_rng(seed)
    records = []
    truths = []
    for i in range(n):
      feats = {
          "action": [float(x) for x in rng.standard_normal(4)],
          "step": [int(i), int(i + 1)],
      }
      if raw_bytes:
        feats["state"] = [rng.standard_normal(3).astype(np.float32)
                          .tobytes()]
      if image:
        feats["image"] = [_jpeg_bytes(h=32, w=32, seed=i)]
      truths.append(feats)
      records.append(example_proto.encode_example(feats))
    return records, truths

  def test_dense_float_and_int_parity(self):
    lib = native.get_native()
    records, truths = self._records()
    floats = lib.example_batch_dense(records, "action", 2, 4)
    np.testing.assert_allclose(
        floats, np.asarray([t["action"] for t in truths], np.float32))
    ints = lib.example_batch_dense(records, "step", 3, 2)
    assert ints.dtype == np.int64
    np.testing.assert_array_equal(
        ints, np.asarray([t["step"] for t in truths]))

  def test_dense_mismatches_return_none(self):
    lib = native.get_native()
    records, _ = self._records()
    assert lib.example_batch_dense(records, "missing", 2, 4) is None
    assert lib.example_batch_dense(records, "action", 3, 4) is None  # kind
    assert lib.example_batch_dense(records, "action", 2, 5) is None  # count

  def test_malformed_proto_raises(self):
    lib = native.get_native()
    with pytest.raises(ValueError, match="[Mm]alformed"):
      lib.example_batch_dense([b"\x0a\xff\xff\xff\xff\x7f"], "x", 2, 1)

  def test_bytes_extraction(self):
    lib = native.get_native()
    records, truths = self._records(raw_bytes=True)
    blobs = lib.example_batch_bytes(records, "state")
    assert blobs == [t["state"][0] for t in truths]

  def test_negative_int64_round_trip(self):
    lib = native.get_native()
    rec = example_proto.encode_example({"v": [-5, -1, 3]})
    out = lib.example_batch_dense([rec], "v", 3, 3)
    np.testing.assert_array_equal(out[0], [-5, -1, 3])

  def test_parser_uses_native_path_and_matches_python(self, monkeypatch):
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    records, _ = self._records(image=True, raw_bytes=True)
    feature_spec = ts.TensorSpecStruct({
        "image": ts.ExtendedTensorSpec((32, 32, 3), np.uint8,
                                       name="image", data_format="jpeg"),
        "action": ts.ExtendedTensorSpec((4,), np.float32, name="action"),
        "state": ts.ExtendedTensorSpec((3,), np.float32, name="state"),
    })
    label_spec = ts.TensorSpecStruct({
        "step": ts.ExtendedTensorSpec((2,), np.int32, name="step"),
    })
    p = parser.ExampleParser(feature_spec, label_spec)
    assert p._native_plan is not None  # the fast path is live
    feats_n, labels_n = p.parse_batch(records)
    # Force the Python codec and compare bit-for-bit.
    p2 = parser.ExampleParser(feature_spec, label_spec)
    monkeypatch.setattr(p2, "_native_plan_cache", None)
    feats_p, labels_p = p2.parse_batch(records)
    assert set(feats_n) == set(feats_p)
    for k in feats_n:
      np.testing.assert_array_equal(feats_n[k], feats_p[k])
      assert feats_n[k].dtype == feats_p[k].dtype
    np.testing.assert_array_equal(labels_n["step"], labels_p["step"])
    assert labels_n["step"].dtype == np.int32

  def test_parser_plan_ineligible_for_varlen_and_optional(self):
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    p = parser.ExampleParser(ts.TensorSpecStruct({
        "seq": ts.ExtendedTensorSpec((5, 2), np.float32, name="seq",
                                     is_sequence=True)}))
    assert p._native_plan is None
    p = parser.ExampleParser(ts.TensorSpecStruct({
        "opt": ts.ExtendedTensorSpec((2,), np.float32, name="opt",
                                     is_optional=True)}))
    assert p._native_plan is None

  def test_parser_falls_back_on_missing_feature(self):
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    records = [example_proto.encode_example({"other": [1.0]})]
    p = parser.ExampleParser(ts.TensorSpecStruct({
        "action": ts.ExtendedTensorSpec((1,), np.float32, name="action")}))
    with pytest.raises(ValueError, match="missing required feature"):
      p.parse_batch(records)

  def test_speed_vs_python(self):
    lib = native.get_native()
    records, _ = self._records(n=256, seed=1)
    start = time.perf_counter()
    for _ in range(20):
      lib.example_batch_dense(records, "action", 2, 4)
    native_t = time.perf_counter() - start
    start = time.perf_counter()
    for _ in range(20):
      np.stack([np.asarray(
          example_proto.decode_example(r)["action"], np.float32)
          for r in records])
    python_t = time.perf_counter() - start
    assert native_t < python_t, (native_t, python_t)


class TestExampleParseParity:
  """Wire-level edge cases where the C++ and Python codecs must agree."""

  @staticmethod
  def _varint(v):
    out = bytearray()
    while True:
      b = v & 0x7F
      v >>= 7
      out.append(b | 0x80 if v else b)
      if not v:
        return bytes(out)

  def _example(self, feature_payload, name=b"a"):
    v = self._varint
    entry = (b"\x0a" + v(len(name)) + name
             + b"\x12" + v(len(feature_payload)) + feature_payload)
    features = b"\x0a" + v(len(entry)) + entry
    return b"\x0a" + v(len(features)) + features

  def _float_list(self, values, trailing=b""):
    import struct
    packed = struct.pack(f"<{len(values)}f", *values) + trailing
    payload = b"\x0a" + self._varint(len(packed)) + packed
    return b"\x12" + self._varint(len(payload)) + payload

  def test_duplicate_oneof_first_wins_both_paths(self):
    lib = native.get_native()
    feature = self._float_list([1.0, 2.0]) + self._float_list([9.0, 9.0])
    record = self._example(feature)
    assert example_proto.decode_example(record)["a"] == [1.0, 2.0]
    out = lib.example_batch_dense([record], "a", 2, 2)
    np.testing.assert_array_equal(out[0], [1.0, 2.0])

  def test_trailing_packed_bytes_ignored_both_paths(self):
    lib = native.get_native()
    record = self._example(
        self._float_list([1.0, 2.0, 3.0, 4.0], trailing=b"\xab\xcd"))
    assert example_proto.decode_example(record)["a"] == [1.0, 2.0, 3.0, 4.0]
    out = lib.example_batch_dense([record], "a", 2, 4)
    np.testing.assert_array_equal(out[0], [1.0, 2.0, 3.0, 4.0])

  def test_grayscale_jpeg_with_rgb_spec_parses_same_both_paths(self):
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    gray = _jpeg_bytes(h=32, w=32, seed=5, gray=True)
    records = [example_proto.encode_example({"image": [gray]})]
    spec = ts.TensorSpecStruct({
        "image": ts.ExtendedTensorSpec((32, 32, 3), np.uint8,
                                       name="image", data_format="jpeg")})
    p_native = parser.ExampleParser(spec)
    assert p_native._native_plan is not None
    feats_n, _ = p_native.parse_batch(records)
    p_python = parser.ExampleParser(spec)
    p_python._native_plan_cache = None
    feats_p, _ = p_python.parse_batch(records)
    # Both paths convert to the spec's channel count (TF decode_jpeg
    # semantics) — neither works-on-one-machine-crashes-on-another.
    np.testing.assert_array_equal(feats_n["image"], feats_p["image"])

  def test_multi_route_outputs_do_not_alias(self):
    from tensor2robot_tpu.specs import tensorspec_utils as ts
    records = [example_proto.encode_example({"pose": [1.0, 2.0]})]
    spec = ts.ExtendedTensorSpec((2,), np.float32, name="pose")
    p = parser.ExampleParser(
        ts.TensorSpecStruct({"pose": spec}),
        ts.TensorSpecStruct({"pose": spec}))
    assert p._native_plan is not None
    feats, labels = p.parse_batch(records)
    feats["pose"][0, 0] = 99.0
    assert labels["pose"][0, 0] == 1.0


class TestBuildCache:
  """Staleness is content-hash keyed (ADVICE r3): a .so whose mtime is
  newer than the source but whose recorded source hash mismatches must
  be treated as stale — mtime ordering says nothing about provenance."""

  def test_current_library_matches_hash(self):
    from tensor2robot_tpu.data import build_native
    if not os.path.exists(build_native.LIBRARY):
      pytest.skip("native library not built")
    assert build_native.library_is_current()

  def test_binary_is_portable_across_hosts(self):
    """The tree (ignored build products included) is copied between
    hosts: a -march=native binary died with SIGILL on the v5e host."""
    from tensor2robot_tpu.data import build_native
    assert not any("march" in flag or "mtune" in flag
                   for flag in build_native._BUILD_CMD)

  def test_hash_covers_the_build_command(self, monkeypatch):
    """A .so built from the same source under other flags is stale."""
    from tensor2robot_tpu.data import build_native
    before = build_native.source_hash()
    monkeypatch.setattr(build_native, "_BUILD_CMD",
                        build_native._BUILD_CMD + ("-march=native",))
    assert build_native.source_hash() != before

  def test_stale_library_is_rebuilt_never_loaded(self, monkeypatch,
                                                 tmp_path):
    """get_native() trusts the .so only after library_is_current();
    with a stale sidecar and a failing build the answer is None — the
    stale binary is not loaded as a consolation."""
    from tensor2robot_tpu.data import build_native, native
    fake_lib = tmp_path / "lib.so"
    fake_lib.write_bytes(b"built elsewhere, for another CPU")
    monkeypatch.setattr(build_native, "LIBRARY", str(fake_lib))
    monkeypatch.setattr(build_native, "HASH_SIDECAR",
                        str(fake_lib) + ".srchash")

    def failing_build(verbose=True):
      raise RuntimeError("no compiler here")
    monkeypatch.setattr(build_native, "build", failing_build)
    loaded = []
    monkeypatch.setattr(native.ctypes, "CDLL",
                        lambda path: loaded.append(path))
    native.reset_cache()
    try:
      assert native.get_native() is None
      assert loaded == []
    finally:
      native.reset_cache()

  def test_missing_sidecar_means_stale(self, monkeypatch, tmp_path):
    from tensor2robot_tpu.data import build_native
    fake_lib = tmp_path / "lib.so"
    fake_lib.write_bytes(b"not a real so")
    monkeypatch.setattr(build_native, "LIBRARY", str(fake_lib))
    monkeypatch.setattr(build_native, "HASH_SIDECAR",
                        str(fake_lib) + ".srchash")
    assert not build_native.library_is_current()

  def test_hash_mismatch_means_stale_despite_newer_mtime(
      self, monkeypatch, tmp_path):
    from tensor2robot_tpu.data import build_native
    fake_lib = tmp_path / "lib.so"
    fake_lib.write_bytes(b"artifact built from older source")
    sidecar = tmp_path / "lib.so.srchash"
    sidecar.write_text("0" * 64 + "\n")  # hash of some OTHER source
    monkeypatch.setattr(build_native, "LIBRARY", str(fake_lib))
    monkeypatch.setattr(build_native, "HASH_SIDECAR", str(sidecar))
    # mtime ordering would call this fresh; the hash says otherwise.
    now = time.time()
    os.utime(fake_lib, (now + 100, now + 100))
    assert not build_native.library_is_current()
