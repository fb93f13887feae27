"""Ingest-layer tests: parsing, reading, prefixes, trie."""

import gzip

import pytest

from rdfind_tpu.io import ntriples, prefixes, reader
from rdfind_tpu.utils.trie import StringTrie


def test_parse_iri_triple():
    s, p, o = ntriples.parse_line("<http://a> <http://b> <http://c> .")
    assert (s, p, o) == ("<http://a>", "<http://b>", "<http://c>")


def test_parse_literals():
    line = '<http://a> <http://b> "hello world" .'
    assert ntriples.parse_line(line)[2] == '"hello world"'
    line = '<http://a> <http://b> "hi"@en .'
    assert ntriples.parse_line(line)[2] == '"hi"@en'
    line = '<http://a> <http://b> "5"^^<http://int> .'
    assert ntriples.parse_line(line)[2] == '"5"^^<http://int>'
    line = r'<http://a> <http://b> "esc\"aped" .'
    assert ntriples.parse_line(line)[2] == r'"esc\"aped"'


def test_parse_blank_nodes_and_quads():
    s, p, o = ntriples.parse_line("_:b1 <http://p> _:b2 .")
    assert (s, p, o) == ("_:b1", "<http://p>", "_:b2")
    s, p, o = ntriples.parse_line(
        "<http://s> <http://p> <http://o> <http://graph> .", expect_quad=True)
    assert (s, p, o) == ("<http://s>", "<http://p>", "<http://o>")


def test_parse_blank_and_errors():
    assert ntriples.parse_line("   ") is None
    with pytest.raises(ntriples.ParseError):
        ntriples.parse_line("<http://a> <http://b> .")
    with pytest.raises(ntriples.ParseError):
        ntriples.parse_line('<http://a> <http://b> "unterminated .')


def test_parse_tabs():
    assert ntriples.parse_tab_line("a\tb\tc") == ("a", "b", "c")
    assert ntriples.parse_tab_line("  ") is None


def test_reader_gz_and_comments(tmp_path):
    plain = tmp_path / "a.nt"
    plain.write_text("# comment\n<s1> <p> <o> .\n")
    gz = tmp_path / "b.nt.gz"
    with gzip.open(gz, "wt") as f:
        f.write("<s2> <p> <o> .\n# another\n")
    paths = reader.resolve_path_patterns([str(tmp_path / "*.nt*")])
    lines = list(reader.iter_lines(paths))
    assert [(fid, ln.split()[0]) for fid, ln in lines] == [(0, "<s1>"), (1, "<s2>")]


def test_reader_missing_file():
    with pytest.raises(FileNotFoundError):
        reader.resolve_path_patterns(["/nonexistent/xyz*.nt"])


def test_trie_longest_prefix():
    t = StringTrie()
    t["http://dbpedia.org/resource/"] = "dbr:"
    t["http://dbpedia.org/"] = "dbp:"
    t["http://example.org/"] = "ex:"
    for squash in (False, True):
        if squash:
            t.squash()
        assert t.longest_prefix_value("http://dbpedia.org/resource/Berlin") == "dbr:"
        assert t.longest_prefix_value("http://dbpedia.org/ontology/x") == "dbp:"
        assert t.longest_prefix_value("http://example.org/a") == "ex:"
        assert t.longest_prefix_value("http://other.org/") is None


def test_prefix_parse_and_shorten():
    pair = prefixes.parse_prefix_line("@prefix dbr: <http://dbpedia.org/resource/> .")
    assert pair == ("dbr:", "http://dbpedia.org/resource/")
    assert prefixes.parse_prefix_line("# not a prefix") is None
    trie = prefixes.build_prefix_trie([pair])
    urls = dict([pair])
    assert prefixes.shorten_term("<http://dbpedia.org/resource/Berlin>", trie, urls) \
        == "dbr:Berlin"
    assert prefixes.shorten_term('"literal"', trie, urls) == '"literal"'
    assert prefixes.shorten_term("<http://other/x>", trie, urls) == "<http://other/x>"


def test_asciify():
    assert prefixes.asciify("plain") == "plain"
    assert prefixes.asciify("Zürich") == "Zurich"
    assert prefixes.asciify("日本") == "??"


def test_reader_name_filter(tmp_path):
    (tmp_path / "a.nt").write_text("<s1> <p> <o> .\n")
    (tmp_path / "b.nt").write_text("<s2> <p> <o> .\n")
    (tmp_path / "skip.txt").write_text("junk\n")
    paths = reader.resolve_path_patterns([str(tmp_path)], name_filter=r"\.nt$")
    assert [p.rsplit("/", 1)[1] for p in paths] == ["a.nt", "b.nt"]
    with pytest.raises(FileNotFoundError):
        reader.resolve_path_patterns([str(tmp_path)], name_filter=r"\.nope$")


def test_reader_bom_sniff(tmp_path):
    utf16 = tmp_path / "a.nt"
    utf16.write_bytes('<s1> <p> "héllo" .\n'.encode("utf-16"))  # LE BOM
    utf8sig = tmp_path / "b.nt"
    utf8sig.write_bytes('<s2> <p> "x" .\n'.encode("utf-8-sig"))
    plain = tmp_path / "c.nt"
    plain.write_text('<s3> <p> "y" .\n')
    assert reader.sniff_encoding(str(utf16)) == "utf-16"
    assert reader.sniff_encoding(str(utf8sig)) == "utf-8-sig"
    assert reader.sniff_encoding(str(plain)) == "utf-8"
    lines = list(reader.iter_lines(
        [str(utf16), str(utf8sig), str(plain)], encoding="auto"))
    # BOMs are stripped, content decodes per-file.
    assert [ln.split()[0] for _, ln in lines] == ["<s1>", "<s2>", "<s3>"]
    assert "héllo" in lines[0][1]


def test_reader_per_file_encodings(tmp_path):
    latin = tmp_path / "latin.nt"
    latin.write_bytes('<s1> <p> "café" .\n'.encode("latin-1"))
    utf8 = tmp_path / "u.nt"
    utf8.write_text('<s2> <p> "naïve" .\n')
    enc = {"latin.nt": "latin-1", None: "utf-8"}
    lines = dict(reader.iter_lines([str(latin), str(utf8)], encoding=enc))
    assert "café" in lines[0] and "naïve" in lines[1]
    # Callable spec.
    lines2 = dict(reader.iter_lines(
        [str(latin), str(utf8)],
        encoding=lambda p: "latin-1" if "latin" in p else "utf-8"))
    assert lines2 == lines


def test_reader_gz_bom_sniff(tmp_path):
    gz = tmp_path / "a.nt.gz"
    with gzip.open(gz, "wb") as f:
        f.write('<s1> <p> "zür" .\n'.encode("utf-16"))
    assert reader.sniff_encoding(str(gz)) == "utf-16"
    (_, line), = reader.iter_lines([str(gz)], encoding="auto")
    assert "zür" in line


def test_reader_callable_auto_encoding(tmp_path):
    f = tmp_path / "a.nt"
    f.write_bytes('<s1> <p> "é" .\n'.encode("utf-16"))
    assert reader.encoding_for(str(f), lambda p: "auto") == "utf-16"
    (_, line), = reader.iter_lines([str(f)], encoding=lambda p: "auto")
    assert "é" in line


def test_is_utf8_aliases():
    """UTF-8 aliases enable the native path; auto/unknown/non-utf8 do not."""
    assert reader.is_utf8("utf-8")
    assert reader.is_utf8("UTF-8")
    assert reader.is_utf8("utf8")
    assert reader.is_utf8("U8")
    assert not reader.is_utf8("auto")
    assert not reader.is_utf8("latin-1")
    assert not reader.is_utf8("no-such-codec")
    assert not reader.is_utf8({"a.nt": "utf-8"})
    assert not reader.is_utf8(None)


@pytest.mark.parametrize("high", [1000, 10 ** 10])
def test_write_nt_matches_token_spelling(tmp_path, high):
    """The vectorized writer spells every row exactly as
    synth.triples_to_tokens does; ids past nine digits take the per-row
    path."""
    import numpy as np

    from rdfind_tpu.utils import synth

    t = np.random.default_rng(1).integers(0, high, (300, 3))
    path = tmp_path / "t.nt"
    synth.write_nt(str(path), t, chunk_rows=128)
    want = "".join(f"{s} {p} {o} .\n"
                   for s, p, o in synth.triples_to_tokens(t))
    assert path.read_text() == want

