"""The LUBM university data, as the yardstick makes it.

The Lehigh University Benchmark (Guo, Pan and Heflin, J. Web Semantics
3(2), 2005) generates its data with UBA from a fixed profile: per
university 15-25 departments, per department 7-10 full, 10-14 associate
and 8-11 assistant professors and 5-7 lecturers, 8-14 undergraduates
and 3-4 graduate students per faculty member, and so on.  The ranges
come from the configuration file (``graph.profile``); this module draws
the entities and their edges from them, one department at a time, with
numpy's generator seeded by the configuration's graph seed.  UBA's own
Java random stream is not reproduced: the same profile gives data of
the same shape, not the same triples.

Vertex ids: the ``CLASSES`` first (so a query names a class by its
position), then every university a degree may name, then each
department's entities and literals as they are drawn.  Literals are
vertices, as in any dictionary-encoded store: a name is shared by every
entity that has it ("FullProfessor3" in every department), an e-mail
address is one person's, and UBA's telephone is the one literal
``xxx-xxx-xxxx``.  Only asserted triples are generated: no inference.
"""
from __future__ import annotations

from typing import Dict, List, Tuple

import numpy as np

#: the univ-bench classes that have instances, in vertex-id order
CLASSES = ["University", "Department", "FullProfessor", "AssociateProfessor",
           "AssistantProfessor", "Lecturer", "UndergraduateStudent",
           "GraduateStudent", "Course", "GraduateCourse", "ResearchGroup",
           "Publication"]

#: the univ-bench properties UBA asserts, in property-id order
PROPERTIES = ["type", "name", "emailAddress", "telephone", "researchInterest",
              "subOrganizationOf", "worksFor", "memberOf", "headOf",
              "teacherOf", "takesCourse", "teachingAssistantOf", "advisor",
              "publicationAuthor", "undergraduateDegreeFrom",
              "mastersDegreeFrom", "doctoralDegreeFrom"]

FACULTY = ["FullProfessor", "AssociateProfessor", "AssistantProfessor",
           "Lecturer"]
#: faculty ranks that advise students and co-author with them
PROFESSORS = FACULTY[:3]

P = {name: i for i, name in enumerate(PROPERTIES)}
C = {name: i for i, name in enumerate(CLASSES)}


def named_vertices(graph: Dict) -> Dict[str, int]:
    """Name -> vertex id of every vertex a query may name: the classes."""
    return dict(C)


def property_names(graph: Dict) -> List[str]:
    return list(PROPERTIES)


class _Graph:
    """Triples and vertex ids as they are drawn."""

    def __init__(self, first_free: int) -> None:
        self.next_id = first_free
        self.parts: List[Tuple[int, np.ndarray, np.ndarray]] = []
        self.literals: Dict[str, int] = {}

    def new(self, n: int) -> np.ndarray:
        ids = np.arange(self.next_id, self.next_id + n, dtype=np.int64)
        self.next_id += n
        return ids

    def literal(self, text: str) -> int:
        if text not in self.literals:
            self.literals[text] = int(self.new(1)[0])
        return self.literals[text]

    def names(self, cls: str, n: int) -> np.ndarray:
        """The name literals ``<cls>0`` .. ``<cls><n-1>``."""
        return np.array([self.literal(f"{cls}{k}") for k in range(n)],
                        np.int64)

    def add(self, prop: str, s, o) -> None:
        s, o = np.broadcast_arrays(np.asarray(s, np.int64),
                                   np.asarray(o, np.int64))
        if s.size:
            self.parts.append((P[prop], s.ravel(), o.ravel()))

    def entities(self, cls: str, n: int, named: bool = True) -> np.ndarray:
        """``n`` new entities of ``cls``, typed and (if ``named``) named."""
        ids = self.new(n)
        self.add("type", ids, C[cls])
        if named:
            self.add("name", ids, self.names(cls, n))
        return ids


def _between(rng, lo_hi) -> int:
    lo, hi = lo_hi
    return int(rng.integers(int(lo), int(hi) + 1))


def _pick(rng, n_from: int, counts: np.ndarray) -> Tuple[np.ndarray,
                                                         np.ndarray]:
    """For each row ``i``, ``counts[i]`` distinct picks from
    ``range(n_from)``: (row index, pick), each of a row's picks once."""
    k = int(counts.max(initial=0))
    if k == 0 or n_from == 0:
        return np.zeros(0, np.int64), np.zeros(0, np.int64)
    k = min(k, n_from)
    picks = np.argsort(rng.random((len(counts), n_from)), axis=1)[:, :k]
    keep = np.arange(k)[None, :] < np.minimum(counts, n_from)[:, None]
    rows = np.broadcast_to(np.arange(len(counts))[:, None], picks.shape)
    return rows[keep].astype(np.int64), picks[keep].astype(np.int64)


def _department(g: _Graph, rng, prof: Dict, univ: int, dept_no: int,
                degree_univs: np.ndarray) -> None:
    dept = g.new(1)
    g.add("type", dept, C["Department"])
    g.add("name", dept, g.literal(f"Department{dept_no}"))
    g.add("subOrganizationOf", dept, univ)
    domain = f"@Department{dept_no}.University{univ - degree_univs[0]}.edu"

    def people(cls: str, n: int) -> np.ndarray:
        ids = g.entities(cls, n)
        g.add("emailAddress", ids,
              [g.literal(f"{cls}{k}{domain}") for k in range(n)])
        g.add("telephone", ids, g.literal("xxx-xxx-xxxx"))
        return ids

    faculty = {cls: people(cls, _between(rng, prof["faculty"][cls]))
               for cls in FACULTY}
    fac = np.concatenate([faculty[c] for c in FACULTY])
    profs = np.concatenate([faculty[c] for c in PROFESSORS])
    g.add("worksFor", fac, dept)
    g.add("headOf", faculty["FullProfessor"][:1], dept)
    interests = np.array([g.literal(f"Research{k}") for k in
                          range(int(prof["research_interests"]))])
    g.add("researchInterest", fac,
          interests[rng.integers(0, len(interests), len(fac))])
    for prop in ("undergraduateDegreeFrom", "mastersDegreeFrom",
                 "doctoralDegreeFrom"):
        g.add(prop, fac,
              degree_univs[rng.integers(0, len(degree_univs), len(fac))])

    courses = {}
    for cls, key in (("Course", "courses_per_faculty"),
                     ("GraduateCourse", "graduate_courses_per_faculty")):
        lo, hi = prof[key]
        per = rng.integers(int(lo), int(hi) + 1, len(fac))
        courses[cls] = g.entities(cls, int(per.sum()))
        g.add("teacherOf", np.repeat(fac, per), courses[cls])

    ug = people("UndergraduateStudent",
                len(fac) * _between(rng, prof["undergraduates_per_faculty"]))
    gs = people("GraduateStudent",
                len(fac) * _between(rng, prof["graduates_per_faculty"]))
    students = np.concatenate([ug, gs])
    g.add("memberOf", students, dept)
    for who, cls, key in ((ug, "Course", "undergraduate_courses_taken"),
                          (gs, "GraduateCourse", "graduate_courses_taken")):
        lo, hi = prof[key]
        rows, picks = _pick(rng, len(courses[cls]),
                            rng.integers(int(lo), int(hi) + 1, len(who)))
        g.add("takesCourse", who[rows], courses[cls][picks])
    g.add("undergraduateDegreeFrom", gs,
          degree_univs[rng.integers(0, len(degree_univs), len(gs))])

    advised = rng.permutation(ug)[:int(round(
        len(ug) * float(prof["undergraduate_advisor_share"])))]
    g.add("advisor", advised, profs[rng.integers(0, len(profs),
                                                 len(advised))])
    g.add("advisor", gs, profs[rng.integers(0, len(profs), len(gs))])
    lo, hi = prof["teaching_assistant_share"]
    n_ta = _between(rng, (int(np.ceil(len(gs) * lo)),
                          int(np.floor(len(gs) * hi))))
    tas = rng.permutation(gs)[:n_ta]
    g.add("teachingAssistantOf", tas,
          rng.permutation(courses["Course"])[:n_ta]
          if n_ta <= len(courses["Course"])
          else rng.choice(courses["Course"], n_ta))

    groups = g.entities("ResearchGroup", _between(
        rng, prof["research_groups_per_department"]), named=False)
    g.add("subOrganizationOf", groups, dept)

    prof_pubs = []
    for cls in FACULTY:
        lo, hi = prof["publications"][cls]
        per = rng.integers(int(lo), int(hi) + 1, len(faculty[cls]))
        for author, n in zip(faculty[cls], per):
            pubs = g.entities("Publication", int(n))
            g.add("publicationAuthor", pubs, author)
            if cls in PROFESSORS:
                prof_pubs.append(pubs)
    pool = np.concatenate(prof_pubs) if prof_pubs else np.zeros(0, np.int64)
    lo, hi = prof["graduate_coauthored_publications"]
    rows, picks = _pick(rng, len(pool),
                        rng.integers(int(lo), int(hi) + 1, len(gs)))
    g.add("publicationAuthor", pool[picks], gs[rows])


def generate_graph(graph: Dict) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, int]:
    """(s, p, o, number of vertex ids) of LUBM(``universities``, seed)."""
    prof = graph["profile"]
    rng = np.random.default_rng(int(graph["seed"]))
    g = _Graph(len(CLASSES))
    degree_univs = g.new(int(prof["degree_universities"]))
    for u in range(int(graph["universities"])):
        univ = int(degree_univs[u])
        g.add("type", univ, C["University"])
        g.add("name", univ, g.literal(f"University{u}"))
        for d in range(_between(rng, prof["departments_per_university"])):
            _department(g, rng, prof, univ, d, degree_univs)
    p = np.concatenate([np.full(len(s), pid, np.int64)
                        for pid, s, _o in g.parts])
    s = np.concatenate([s for _pid, s, _o in g.parts])
    o = np.concatenate([o for _pid, _s, o in g.parts])
    nv = g.next_id
    key = (p * nv + s) * nv + o
    _, keep = np.unique(key, return_index=True)
    keep.sort()
    return (s[keep].astype(np.int32), p[keep].astype(np.int32),
            o[keep].astype(np.int32), nv)
