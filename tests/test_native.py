"""Native C++ runtime tests: loader, binary reader, zip, sampling.

The native reader must agree record-for-record with the pure-Python
fallback (engine parity is the contract that makes `auto` safe).
"""

import os
import zipfile

import numpy as np
import pytest

from mmlspark_tpu.native import native_available


needs_native = pytest.mark.skipif(
    not native_available(), reason="g++/zlib toolchain unavailable")


@pytest.fixture
def tree(tmp_path):
    """A small directory tree with nested dirs, a zip, and an empty file."""
    (tmp_path / "sub" / "deeper").mkdir(parents=True)
    rng = np.random.default_rng(7)
    files = {
        "a.bin": rng.bytes(1000),
        "b.txt": b"hello world",
        "sub/c.bin": rng.bytes(50_000),
        "sub/deeper/d.bin": rng.bytes(3),
        "empty.bin": b"",
    }
    for rel, data in files.items():
        (tmp_path / rel).write_bytes(data)
    with zipfile.ZipFile(tmp_path / "arch.zip", "w",
                         compression=zipfile.ZIP_DEFLATED) as zf:
        zf.writestr("inner/x.bin", rng.bytes(5000))
        zf.writestr("y.txt", b"zipped text")
    with zipfile.ZipFile(tmp_path / "stored.zip", "w",
                         compression=zipfile.ZIP_STORED) as zf:
        zf.writestr("s.bin", rng.bytes(128))
    return tmp_path


@needs_native
class TestNativeReader:
    def test_matches_python_engine(self, tree):
        from mmlspark_tpu.io.binary import read_binary_files
        nat = read_binary_files(str(tree), engine="native")
        py = read_binary_files(str(tree), engine="python")
        assert list(nat["path"]) == list(py["path"])
        for a, b in zip(nat["bytes"], py["bytes"]):
            assert a == b
        # zip members present (deflate + stored), empty file kept
        paths = list(nat["path"])
        assert any(p.endswith("arch.zip/inner/x.bin") for p in paths)
        assert any(p.endswith("stored.zip/s.bin") for p in paths)
        assert any(p.endswith("empty.bin") for p in paths)

    def test_pattern_and_nonrecursive(self, tree):
        from mmlspark_tpu.io.binary import read_binary_files
        for kw in ({"pattern": "*.bin", "inspect_zip": False},
                   {"recursive": False, "inspect_zip": False}):
            nat = read_binary_files(str(tree), engine="native", **kw)
            py = read_binary_files(str(tree), engine="python", **kw)
            assert list(nat["path"]) == list(py["path"])

    def test_sampling_deterministic(self, tree):
        from mmlspark_tpu.io.binary import read_binary_files
        a = read_binary_files(str(tree), engine="native", sample_ratio=0.5,
                              seed=1)
        b = read_binary_files(str(tree), engine="native", sample_ratio=0.5,
                              seed=1)
        assert list(a["path"]) == list(b["path"])
        full = read_binary_files(str(tree), engine="native")
        assert a.num_rows <= full.num_rows

    def test_many_files_prefetch(self, tmp_path):
        """More files than the prefetch window, several workers."""
        from mmlspark_tpu.native import native_read_records
        for i in range(100):
            (tmp_path / f"f{i:03d}.bin").write_bytes(bytes([i % 256]) * i)
        recs = list(native_read_records(str(tmp_path), n_threads=8,
                                        prefetch_files=4))
        assert len(recs) == 100
        for i, (p, data) in enumerate(recs):
            assert p.endswith(f"f{i:03d}.bin")
            assert data == bytes([i % 256]) * i

    def test_single_file_root(self, tree):
        from mmlspark_tpu.native import native_read_records
        recs = list(native_read_records(str(tree / "b.txt")))
        assert len(recs) == 1 and recs[0][1] == b"hello world"

    def test_empty_deflated_member(self, tmp_path):
        """Empty members compressed with deflate must parse as b''."""
        from mmlspark_tpu.io.binary import read_binary_files
        with zipfile.ZipFile(tmp_path / "e.zip", "w",
                             compression=zipfile.ZIP_DEFLATED) as zf:
            zf.writestr("empty.txt", b"")
            zf.writestr("full.txt", b"data")
        nat = read_binary_files(str(tmp_path), engine="native")
        py = read_binary_files(str(tmp_path), engine="python")
        assert list(nat["path"]) == list(py["path"])
        assert list(nat["bytes"]) == list(py["bytes"])

    def test_missing_path_raises_like_python(self, tmp_path):
        from mmlspark_tpu.io.binary import read_binary_files
        for engine in ("native", "python"):
            with pytest.raises(FileNotFoundError):
                read_binary_files(str(tmp_path / "nope"), engine=engine)

    def test_corrupt_zip_raises(self, tmp_path):
        from mmlspark_tpu.native import native_read_records
        (tmp_path / "bad.zip").write_bytes(b"PK\x03\x04 this is not a zip")
        with pytest.raises(IOError):
            list(native_read_records(str(tmp_path)))


class TestLoader:
    def test_unknown_library(self):
        from mmlspark_tpu.native.loader import NativeLoader
        with pytest.raises(Exception):
            NativeLoader.load_library_by_name("no_such_lib")

    @needs_native
    def test_build_is_keyed_on_sources_not_mtime(self, tmp_path,
                                                 monkeypatch):
        """A binary that merely SITS in the build dir (copied along
        with a checkout, newer than the sources) is never loaded: the
        cached name carries a hash of the sources and the flags."""
        import ctypes
        from mmlspark_tpu.native import loader
        monkeypatch.setattr(loader, "_BUILD_DIR", str(tmp_path))
        stale = tmp_path / "libmmlbinary.so"
        stale.write_bytes(b"not built from these sources")
        built = loader._find_or_build("mmlbinary")
        assert built != str(stale)
        assert os.path.dirname(built) == str(tmp_path)
        assert ctypes.CDLL(built).mml_abi_version() == 1
        assert loader._find_or_build("mmlbinary") == built   # by key

    @needs_native
    def test_cached_handle_identity(self):
        from mmlspark_tpu.native.loader import NativeLoader
        a = NativeLoader.load_library_by_name("mmlbinary")
        b = NativeLoader.load_library_by_name("mmlbinary")
        assert a is b
        assert a.mml_abi_version() == 1
