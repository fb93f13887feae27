"""Native C++ ingest vs. the pure-Python reference path, on identical inputs."""

import gzip

import numpy as np
import pytest

from rdfind_tpu.dictionary import BlobDictionary, Dictionary, intern_triples
from rdfind_tpu.io import native, ntriples, reader

pytestmark = pytest.mark.skipif(not native.available(),
                                reason="native library unavailable")

NT = """\
# a comment line
<http://ex/s1> <http://ex/p1> "plain literal" .
<http://ex/s1> <http://ex/p2> "esc \\" quote"@en .
<http://ex/s2> <http://ex/p1> "typed"^^<http://www.w3.org/2001/XMLSchema#string> .
_:blank1 <http://ex/p3> <http://ex/s1> .

<http://ex/s3> <http://ex/p1> "tab\\tin literal" .
"""

NQ = """\
<http://ex/s1> <http://ex/p1> <http://ex/o1> <http://ex/g1> .
<http://ex/s2> <http://ex/p1> "lit" <http://ex/g2> .
"""


def python_path(paths, tabs=False, expect_quad=False):
    rows = []
    for _, line in reader.iter_lines(paths):
        t = (ntriples.parse_tab_line(line) if tabs
             else ntriples.parse_line(line, expect_quad=expect_quad))
        if t is not None:
            rows.append(t)
    return intern_triples(np.asarray(rows, dtype=object))


def assert_same(got, want):
    ids_n, d_n = got
    ids_p, d_p = want
    np.testing.assert_array_equal(ids_n, ids_p)
    assert list(d_n.values) == list(d_p.values)


def test_ntriples_parity(tmp_path):
    f = tmp_path / "a.nt"
    f.write_text(NT)
    assert_same(native.ingest_files([str(f)]), python_path([str(f)]))


def test_gz_and_multifile_parity(tmp_path):
    f1 = tmp_path / "a.nt"
    f1.write_text(NT)
    f2 = tmp_path / "b.nt.gz"
    with gzip.open(f2, "wt") as g:
        g.write("<http://ex/sX> <http://ex/p1> \"from gz\" .\n")
    paths = [str(f1), str(f2)]
    assert_same(native.ingest_files(paths), python_path(paths))


def test_nquads_parity(tmp_path):
    f = tmp_path / "a.nq"
    f.write_text(NQ)
    assert_same(native.ingest_files([str(f)], expect_quad=True),
                python_path([str(f)], expect_quad=True))


def test_tabs_parity(tmp_path):
    f = tmp_path / "a.tsv"
    f.write_text("s1\tp1\to1\ns2\tp1\to2\n\ns1\tp2\to1\textra ignored\n")
    assert_same(native.ingest_files([str(f)], tabs=True),
                python_path([str(f)], tabs=True))


def test_crlf_and_no_trailing_newline(tmp_path):
    f = tmp_path / "a.nt"
    f.write_bytes(b"<s> <p> <o1> .\r\n<s> <p> <o2> .")
    assert_same(native.ingest_files([str(f)]), python_path([str(f)]))


def test_parse_error_surface(tmp_path):
    f = tmp_path / "bad.nt"
    f.write_text("<http://ex/s1> <http://ex/p1>\n")
    with pytest.raises(native.NativeIngestError, match="expected 3 terms"):
        native.ingest_files([str(f)])
    with pytest.raises(ntriples.ParseError):
        python_path([str(f)])


def test_unterminated_literal_error(tmp_path):
    f = tmp_path / "bad.nt"
    f.write_text('<s> <p> "never closed .\n')
    with pytest.raises(native.NativeIngestError, match="unterminated literal"):
        native.ingest_files([str(f)])


def test_large_random_parity(tmp_path):
    rng = np.random.default_rng(0)
    lines = []
    for _ in range(5000):
        s = f"<http://ex/s{rng.integers(400)}>"
        p = f"<http://ex/p{rng.integers(12)}>"
        kind = rng.integers(3)
        if kind == 0:
            o = f"<http://ex/o{rng.integers(300)}>"
        elif kind == 1:
            o = f'"value {rng.integers(200)}"'
        else:
            o = f"_:b{rng.integers(50)}"
        lines.append(f"{s} {p} {o} .")
    f = tmp_path / "big.nt"
    f.write_text("\n".join(lines) + "\n")
    assert_same(native.ingest_files([str(f)]), python_path([str(f)]))


def test_boundary_spliced_invalid_utf8_parity(tmp_path):
    """Values that are invalid UTF-8 alone but splice into a valid sequence in
    the concatenated dictionary blob (b'a\\xc3' + b'\\xa9b' == 'a' + 'é' + 'b')
    must still decode per-value like the Python path does."""
    f = tmp_path / "splice.tsv"
    f.write_bytes(b"a\xc3\t\xa9b\tZ\n")
    got = native.ingest_files([str(f)], tabs=True)
    want = python_path([str(f)], tabs=True)
    assert_same(got, want)
    # Each invalid value decoded independently (with U+FFFD), never conflated.
    assert len(set(got[1].values)) == len(got[1].values)


@pytest.mark.parametrize("values", [
    [b"abc", b"", b"x"],                     # ASCII, an empty value
    ["héllo".encode(), b"ab", "€x".encode(), b""],  # multi-byte
    [b"a\xc3", b"\xa9b", b"Z"],              # spliced: the blob is valid
    [b"\xff", b"ok"],                        # the blob itself is invalid
])
def test_values_from_buffer_matches_per_value_decode(values):
    """The dictionary blob's split equals a decode of each value alone, and
    `lossless` says whether every value was valid UTF-8 on its own."""
    offsets = np.cumsum([0] + [len(v) for v in values])
    got, lossless = native._values_from_buffer(b"".join(values), offsets)
    assert list(got) == [v.decode(errors="replace") for v in values]
    valid = True
    for v in values:
        try:
            v.decode("utf-8")
        except UnicodeDecodeError:
            valid = False
    assert lossless == valid


@pytest.mark.parametrize("values", [
    [b"<a>", b"<b>", b"lit"],
    [b"ab", "héllo".encode(), "€x".encode()],
    [b"", b"x", b"y"],
    [b"only"],
    [],
    [b"a\xc3", b"\xa9b"],
], ids=["ascii", "multi-byte", "empty-value", "one-value", "empty",
        "spliced"])
def test_blob_dictionary_matches_eager(values):
    """The ingest keeps a lossless blob as a BlobDictionary, which answers
    as the eager decode's Dictionary does; a blob whose values are not each
    valid UTF-8 (the splice) takes the eager, canonicalized path."""
    blob = np.frombuffer(b"".join(values), np.uint8)
    offsets = np.cumsum([0] + [len(v) for v in values]).astype(np.int64)
    ids = np.arange(len(values), dtype=np.int32)
    got_ids, got = native.native_dictionary(ids, blob, offsets)
    want_ids, want = native.canonicalize(
        ids, *native._values_from_buffer(blob, offsets))
    lazy = isinstance(got, BlobDictionary)
    assert lazy == (values[-1:] != [b"\xa9b"])
    np.testing.assert_array_equal(got_ids, want_ids)
    assert len(got) == len(want)
    assert [got.value(i) for i in range(len(got))] == list(want.values)
    if lazy:
        assert got.decoded == len(values)
        if values:
            assert got.value(-1) == want.value(-1)
        with pytest.raises(IndexError):
            got.value(len(values))
    assert got.values.dtype == object
    assert list(got.values) == list(want.values)
    for v in want.values:
        assert got.id(v) == want.id(v)
    np.testing.assert_array_equal(got.ids(want.values), want.ids(want.values))
    for d in (got, want):
        with pytest.raises(KeyError):
            d.id("<missing>")
        if len(d):
            with pytest.raises(KeyError):
                d.ids(["<missing>"])
    assert got == want and want == got
    assert got != Dictionary(np.array(["<other>"], object))


PEOPLE = ("<alice> <bornIn> <berlin> .\n<bob> <bornIn> <berlin> .\n"
          "<zoë> <bornIn> <paris> .\n<alice> <livesIn> <berlin> .\n"
          "<bob> <livesIn> <berlin> .\n<zoë> <livesIn> <paris> .\n"
          "<dave> <livesIn> <rome> .\n").encode()


@pytest.mark.parametrize("data, lazy", [
    (PEOPLE, 1),
    (PEOPLE.replace("ë".encode(), b"\xeb"), 0),  # Latin-1: invalid UTF-8
], ids=["utf8", "invalid-utf8"])
def test_driver_native_output_matches_python_ingest(tmp_path, data, lazy):
    """A native-ingest run writes the --output of a Python-ingest run; its
    dictionary stays the value blob when the input is valid UTF-8, and the
    output then decodes at most the four values of each CIND row."""
    from rdfind_tpu.programs import rdfind

    f = tmp_path / "people.nt"
    f.write_bytes(data)
    outputs, counters = [], []
    for flags in ([], ["--no-native-ingest"]):
        out = tmp_path / f"cinds{len(outputs)}.txt"
        got = []
        assert rdfind.main([str(f), "--support", "2", "--clean-implied",
                            "--output", str(out), *flags],
                           on_result=got.append) == 0
        outputs.append(out.read_text())
        counters.append(got[0].counters)
    assert outputs[0] == outputs[1]
    rows = outputs[0].count("\n")
    assert rows > 0
    assert counters[0]["dictionary-lazy"] == lazy
    decoded = counters[0]["dictionary-decoded"]
    assert 0 < decoded <= 6 * rows if lazy else decoded == 0
    assert counters[1]["dictionary-lazy"] == 0
