"""Input generation for the benchmark: the LUBM generator of the cells'
configuration, small graphs for the tests, and the seeded relabeling that
turns one fixed graph into a distinct input per seed.

`generate_lubm` writes the Lehigh University Benchmark's data by the
published rules of its UBA generator.  `generate_triples` and
`inject_cind_structure` are copied from `rdfind_tpu/utils/synth.py` (as
is `write_nt`) so that a later change to the program cannot move the
yardstick; they make the tests' small graphs and the recorded trace's.
`tests/test_gen.py` pins a digest of each generator's output.

A configuration's graph is built from the configuration's own
`structure_seed`; the run's `--seed` then draws a bijection of the value
ids and an order of the rows.  Every seed thus gives the same sizes (the
same lines, captures and CIND count, hence the same compiled shapes) in
another labelling and order.
"""

from __future__ import annotations

import numpy as np


def generate_triples(n: int, seed: int = 0, n_predicates: int = 24,
                     n_entities: int | None = None) -> np.ndarray:
    """(n, 3) int32 id triples: zipf-ish predicates, subjects and literals;
    objects reuse the subject pool with probability 0.3 (URI objects)."""
    rng = np.random.default_rng(seed)
    if n_entities is None:
        n_entities = max(16, n // 8)
    n_literals = max(16, n // 4)

    ranks = np.arange(1, n_predicates + 1, dtype=np.float64)
    p_pred = (1.0 / ranks) / (1.0 / ranks).sum()
    pred = rng.choice(n_predicates, size=n, p=p_pred).astype(np.int32)
    subj = (rng.zipf(1.3, size=n) % n_entities).astype(np.int32)
    is_uri = rng.random(n) < 0.3
    obj_uri = (rng.zipf(1.3, size=n) % n_entities).astype(np.int32)
    obj_lit = (rng.zipf(1.5, size=n) % n_literals).astype(np.int32)

    subj_ids = subj
    pred_ids = n_entities + pred
    obj_ids = np.where(is_uri, obj_uri, n_entities + n_predicates + obj_lit)
    return np.stack([subj_ids, pred_ids, obj_ids.astype(np.int32)], axis=1)


def inject_cind_structure(triples: np.ndarray, n_rules: int = 32,
                          ref_size: int = 150,
                          dep_size: int = 120) -> np.ndarray:
    """Append `n_rules` planted inclusions s[p=a] < s[p=b] of support
    `dep_size` in fresh id ranges; odd rules share one object on the
    referenced side, which plants family-1/2 CINDs as well."""
    base = int(triples.max()) + 1 if triples.size else 0
    rows = []
    for k in range(n_rules):
        subj = base + np.arange(ref_size, dtype=np.int64)
        pred_a = base + ref_size + 2 * k
        pred_b = pred_a + 1
        obj_b = base + ref_size + 2 * n_rules + np.arange(ref_size)
        obj_a = obj_b + ref_size
        if k % 2 == 0:
            rows.append(np.stack([subj, np.full(ref_size, pred_b), obj_b], 1))
        else:
            hub = obj_b[0]
            rows.append(np.stack([subj, np.full(ref_size, pred_b),
                                  np.full(ref_size, hub)], 1))
        rows.append(np.stack([subj[:dep_size], np.full(dep_size, pred_a),
                              obj_a[:dep_size]], 1))
        base = int(max(obj_a.max(), pred_b)) + 1
    overlay = np.concatenate(rows).astype(np.int32)
    return np.concatenate([np.asarray(triples, np.int32), overlay])


# The Lehigh University Benchmark's data generator (UBA 1.7; Guo, Pan and
# Heflin, "LUBM: A benchmark for OWL knowledge base systems", J. Web
# Semantics 3(2), 2005, section 3.1): its counts per university, department
# and person, each drawn uniformly from the inclusive range.
UBA = {
    "universities_referenced": 1000,  # degrees name any of these
    "departments": (15, 25),
    "full_professors": (7, 10),
    "associate_professors": (10, 14),
    "assistant_professors": (8, 11),
    "lecturers": (5, 7),
    "undergraduates_per_faculty": (8, 14),
    "graduates_per_faculty": (3, 4),
    "courses_per_faculty": (1, 2),
    "graduate_courses_per_faculty": (1, 2),
    "courses_per_undergraduate": (2, 4),
    "courses_per_graduate": (1, 3),
    "research_groups": (10, 20),
    "graduates_per_teaching_assistant": (4, 5),
    "graduates_per_research_assistant": (3, 4),
    "undergraduates_per_advisee": 5,
    "publications": {"FullProfessor": (15, 20),
                     "AssociateProfessor": (10, 18),
                     "AssistantProfessor": (5, 10), "Lecturer": (0, 5),
                     "GraduateStudent": (0, 5)},
    "research_areas": 30,
}

LUBM_PREDICATES = (
    "type", "name", "emailAddress", "telephone", "memberOf", "worksFor",
    "headOf", "teacherOf", "takesCourse", "advisor", "teachingAssistantOf",
    "undergraduateDegreeFrom", "mastersDegreeFrom", "doctoralDegreeFrom",
    "researchInterest", "publicationAuthor", "subOrganizationOf")
FACULTY = ("FullProfessor", "AssociateProfessor", "AssistantProfessor",
           "Lecturer")
LUBM_CLASSES = FACULTY + (
    "University", "Department", "UndergraduateStudent", "GraduateStudent",
    "TeachingAssistant", "ResearchAssistant", "Course", "GraduateCourse",
    "Publication", "ResearchGroup")
# Largest index a name literal can take ("UndergraduateStudent587"): UBA
# names are the class and the index within the department, shared by
# every department.
_MAX_FACULTY = sum(UBA[k][1] for k in ("full_professors",
                                        "associate_professors",
                                        "assistant_professors", "lecturers"))
_NAME_SLOTS = {
    "FullProfessor": UBA["full_professors"][1],
    "AssociateProfessor": UBA["associate_professors"][1],
    "AssistantProfessor": UBA["assistant_professors"][1],
    "Lecturer": UBA["lecturers"][1],
    "UndergraduateStudent": _MAX_FACULTY
    * UBA["undergraduates_per_faculty"][1],
    "GraduateStudent": _MAX_FACULTY * UBA["graduates_per_faculty"][1],
    "Course": _MAX_FACULTY * UBA["courses_per_faculty"][1],
    "GraduateCourse": _MAX_FACULTY * UBA["graduate_courses_per_faculty"][1],
    "Publication": max(hi for _, hi in UBA["publications"].values()),
    "Department": UBA["departments"][1],
    "University": UBA["universities_referenced"],
}


class _Ids:
    """Consecutive id ranges."""

    def __init__(self):
        self.next = 0

    def take(self, n: int) -> int:
        base = self.next
        self.next += int(n)
        return base


def _draw(rng, lo_hi, size=None):
    lo, hi = lo_hi
    return rng.integers(lo, hi + 1, size=size)


def _pick_distinct(rng, n_rows: int, n_choices: int, counts: np.ndarray):
    """(row, choice) pairs: `counts[row]` distinct choices per row."""
    k = int(counts.max()) if n_rows else 0
    k = min(k, n_choices)
    picks = np.argsort(rng.random((n_rows, n_choices)), axis=1)[:, :k]
    keep = np.arange(k)[None, :] < np.minimum(counts, n_choices)[:, None]
    rows = np.broadcast_to(np.arange(n_rows)[:, None], picks.shape)
    return rows[keep], picks[keep]


def generate_lubm(n_universities: int, seed: int = 0,
                  departments: int | None = None) -> np.ndarray:
    """(n, 3) int32 id triples of LUBM(n_universities) by the UBA rules.

    Per department: faculty of four ranks, each with a name, an email, the
    one telephone literal, worksFor, three degrees from any of the 1000
    referenced universities, one or two courses and graduate courses, a
    research interest (professors) and publications; one full professor
    is headOf the department.  Undergraduates (8-14 per faculty) and
    graduates (3-4) have a name, an email, the telephone, memberOf and
    takesCourse; one undergraduate in five and every graduate has a
    professor as advisor; graduates have an undergraduate degree, coauthor
    0-5 of the department's publications, and some are teaching
    assistants of a course or research assistants.  Research
    groups are suborganizations of the department, departments of the
    university.  `departments` fixes the number per university (tests
    only)."""
    rng = np.random.default_rng(seed)
    ids = _Ids()
    pred = {p: ids.take(1) for p in LUBM_PREDICATES}
    cls = {c: ids.take(1) for c in LUBM_CLASSES}
    univ0 = ids.take(UBA["universities_referenced"])
    phone = ids.take(1)
    research0 = ids.take(UBA["research_areas"])
    name0 = {k: ids.take(n) for k, n in _NAME_SLOTS.items()}
    out = []

    def emit(s, p, o):
        s = np.asarray(s, np.int64).ravel()
        o = np.broadcast_to(np.asarray(o, np.int64), s.shape)
        out.append(np.stack([s, np.full(len(s), p, np.int64), o], 1))

    def entities(kind, n):
        """n fresh entities of a class, with their type and name."""
        e = ids.take(n) + np.arange(n)
        emit(e, pred["type"], cls[kind])
        emit(e, pred["name"], name0[kind] + np.arange(n))
        return e

    def person(e):
        emit(e, pred["emailAddress"], ids.take(len(e)) + np.arange(len(e)))
        emit(e, pred["telephone"], phone)

    def degree(e, p):
        emit(e, pred[p], univ0 + rng.integers(
            0, UBA["universities_referenced"], len(e)))

    def publications(authors, rank_range):
        n_pub = _draw(rng, rank_range, len(authors))
        author = np.repeat(authors, n_pub)
        index = np.arange(n_pub.sum()) - np.repeat(np.cumsum(n_pub) - n_pub,
                                                   n_pub)
        pubs = ids.take(len(author)) + np.arange(len(author))
        emit(pubs, pred["type"], cls["Publication"])
        emit(pubs, pred["name"], name0["Publication"] + index)
        emit(pubs, pred["publicationAuthor"], author)
        return pubs

    for u in range(n_universities):
        univ = univ0 + u
        emit([univ], pred["type"], cls["University"])
        emit([univ], pred["name"], name0["University"] + u)
        n_dept = departments or _draw(rng, UBA["departments"])
        for d in range(n_dept):
            dept = ids.take(1)
            emit([dept], pred["type"], cls["Department"])
            emit([dept], pred["name"], name0["Department"] + d)
            emit([dept], pred["subOrganizationOf"], univ)
            ranks = [entities(r, _draw(rng, UBA[k])) for r, k in zip(
                FACULTY, ("full_professors", "associate_professors",
                          "assistant_professors", "lecturers"))]
            faculty = np.concatenate(ranks)
            professors = np.concatenate(ranks[:3])
            n_fac = len(faculty)
            person(faculty)
            emit(faculty, pred["worksFor"], dept)
            for p in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                      "doctoralDegreeFrom"):
                degree(faculty, p)
            emit(professors, pred["researchInterest"], research0
                 + rng.integers(0, UBA["research_areas"], len(professors)))
            emit([ranks[0][rng.integers(len(ranks[0]))]], pred["headOf"],
                 dept)
            faculty_pubs = np.concatenate([
                publications(group, UBA["publications"][rank])
                for rank, group in zip(FACULTY, ranks)])
            courses = {}
            for kind, k in (("Course", "courses_per_faculty"),
                            ("GraduateCourse",
                             "graduate_courses_per_faculty")):
                per = _draw(rng, UBA[k], n_fac)
                courses[kind] = entities(kind, int(per.sum()))
                emit(np.repeat(faculty, per), pred["teacherOf"],
                     courses[kind])

            n_ug = n_fac * _draw(rng, UBA["undergraduates_per_faculty"])
            ug = entities("UndergraduateStudent", n_ug)
            person(ug)
            emit(ug, pred["memberOf"], dept)
            row, pick = _pick_distinct(
                rng, n_ug, len(courses["Course"]),
                _draw(rng, UBA["courses_per_undergraduate"], n_ug))
            emit(ug[row], pred["takesCourse"], courses["Course"][pick])
            advised = rng.integers(0, UBA["undergraduates_per_advisee"],
                                   n_ug) == 0
            emit(ug[advised], pred["advisor"], professors[rng.integers(
                0, len(professors), int(advised.sum()))])

            n_gr = n_fac * _draw(rng, UBA["graduates_per_faculty"])
            gr = entities("GraduateStudent", n_gr)
            person(gr)
            emit(gr, pred["memberOf"], dept)
            degree(gr, "undergraduateDegreeFrom")
            row, pick = _pick_distinct(
                rng, n_gr, len(courses["GraduateCourse"]),
                _draw(rng, UBA["courses_per_graduate"], n_gr))
            emit(gr[row], pred["takesCourse"],
                 courses["GraduateCourse"][pick])
            emit(gr, pred["advisor"],
                 professors[rng.integers(0, len(professors), n_gr)])
            n_ta = min(n_gr // _draw(rng, UBA[
                "graduates_per_teaching_assistant"]), len(courses["Course"]))
            ta = gr[rng.permutation(n_gr)[:n_ta]]
            emit(ta, pred["type"], cls["TeachingAssistant"])
            emit(ta, pred["teachingAssistantOf"],
                 courses["Course"][rng.permutation(
                     len(courses["Course"]))[:n_ta]])
            n_ra = n_gr // _draw(rng, UBA["graduates_per_research_assistant"])
            emit(gr[rng.permutation(n_gr)[:n_ra]], pred["type"],
                 cls["ResearchAssistant"])
            # A graduate's publications are the department's, as coauthor.
            n_pub = _draw(rng, UBA["publications"]["GraduateStudent"], n_gr)
            emit(faculty_pubs[rng.integers(0, len(faculty_pubs),
                                           int(n_pub.sum()))],
                 pred["publicationAuthor"], np.repeat(gr, n_pub))

            n_rg = _draw(rng, UBA["research_groups"])
            groups = ids.take(n_rg) + np.arange(n_rg)
            emit(groups, pred["type"], cls["ResearchGroup"])
            emit(groups, pred["subOrganizationOf"], dept)
    return np.concatenate(out).astype(np.int32)


def build_graph(spec: dict) -> np.ndarray:
    """The configuration's fixed graph, from its `generator` entry."""
    kind = spec["kind"]
    if kind == "lubm":
        return generate_lubm(spec["universities"], seed=spec["structure_seed"],
                             departments=spec.get("departments"))
    if kind == "triples_with_overlay":
        t = generate_triples(spec["n_triples"], seed=spec["structure_seed"],
                             n_predicates=spec["n_predicates"],
                             n_entities=spec["n_entities"])
        return inject_cind_structure(t, **spec["overlay"])
    raise ValueError(f"unknown generator kind {kind!r}")


def relabel(triples: np.ndarray, seed: int) -> np.ndarray:
    """The seed's input: a random bijection of the value ids (one map over
    all three fields, so values shared between fields stay shared) and a
    random row order."""
    rng = np.random.default_rng(seed)
    n_ids = int(triples.max()) + 1
    perm = rng.permutation(n_ids).astype(np.int32)
    order = rng.permutation(len(triples))
    return perm[triples[order]]


_NT_LINE = b"<v000000000> <v000000000> <v000000000> .\n"


def token_id(token: str) -> int:
    """`<v000000123>` -> 123, the inverse of the .nt spelling."""
    return int(token[2:-1])


def write_nt(path, triples: np.ndarray, chunk_rows: int = 1 << 20) -> None:
    """Write id triples as an .nt file of `<v%09d>` URI tokens, formatted
    as fixed-width byte rows in numpy."""
    t = np.asarray(triples, np.int64).reshape(-1, 3)
    if t.size and (t.min() < 0 or t.max() >= 10 ** 9):
        raise ValueError("ids must lie in [0, 10^9) for the .nt spelling")
    line = np.frombuffer(_NT_LINE, np.uint8)
    with open(path, "wb") as f:
        for lo in range(0, len(t), chunk_rows):
            rows = t[lo:lo + chunk_rows]
            out = np.tile(line, (len(rows), 1))
            for field in range(3):
                v = rows[:, field]
                last = 13 * field + 10  # the 9th digit of this token
                for d in range(9):
                    out[:, last - d] = 48 + v % 10
                    v = v // 10
            f.write(out.tobytes())
