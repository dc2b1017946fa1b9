"""The LUBM university data under the univ-bench ontology's entailments.

Most published LUBM evaluations answer its 14 queries over the data and
what the ontology (swat.cse.lehigh.edu/onto/univ-bench.owl) entails:
ten of the queries name a class or property that only inference fills
(``Student``, ``Professor``, ``memberOf`` through ``worksFor``, the
transitive ``subOrganizationOf``, ...).  This generator materializes
those entailments at load, as a store without a reasoner at query time
does.

The asserted triples are ``harness.lubm``'s, unchanged: same
configuration, same seed, same vertex ids, same order.  The classes
that only the ontology names get vertex ids after the asserted ones,
and the properties it adds (``degreeFrom``, ``hasAlumnus``,
``member``) property ids after UBA's.  The entailed triples follow the
asserted ones.  The rules, applied until nothing changes:

* ``SUBPROPERTY``: a triple of the sub-property is one of the super;
* ``INVERSE``: each triple of one property is the other's, reversed;
* ``TRANSITIVE``: ``subOrganizationOf`` chains;
* ``DOMAIN`` and ``RANGE``: the subject (object) of a triple of the
  property is of the class;
* ``SUBCLASS``: a member of the class is one of each super-class;
* ``DEFINED``: a ``Person`` with a triple of the property to a member
  of the filler class is of the defined class (the ``⊒`` half of
  ``Student ≡ Person ⊓ ∃takesCourse.Course`` and its like; their
  ``⊑`` half is in ``SUBCLASS``).
"""
from __future__ import annotations

import functools
import json
from typing import Dict, List, Tuple

import numpy as np

from . import lubm

#: classes only the ontology names, in vertex-id order after the data's
NEW_CLASSES = ["Person", "Employee", "Faculty", "Professor", "Chair",
               "Student", "TeachingAssistant", "Organization", "Work"]
CLASSES = lubm.CLASSES + NEW_CLASSES

#: properties only the ontology names, in property-id order after UBA's
NEW_PROPERTIES = ["degreeFrom", "hasAlumnus", "member"]
PROPERTIES = lubm.PROPERTIES + NEW_PROPERTIES

#: (sub, super) of rdfs:subClassOf, as univ-bench states them
SUBCLASS = [
    ("FullProfessor", "Professor"), ("AssociateProfessor", "Professor"),
    ("AssistantProfessor", "Professor"), ("Chair", "Professor"),
    ("Professor", "Faculty"), ("Lecturer", "Faculty"),
    ("Faculty", "Employee"), ("Employee", "Person"),
    ("UndergraduateStudent", "Student"), ("Student", "Person"),
    ("GraduateStudent", "Person"), ("TeachingAssistant", "Person"),
    ("Chair", "Person"),
    ("GraduateCourse", "Course"), ("Course", "Work"),
    ("University", "Organization"), ("Department", "Organization"),
    ("ResearchGroup", "Organization")]

#: (class, property, filler) of ``class ≡ Person ⊓ ∃property.filler``
DEFINED = [("Student", "takesCourse", "Course"),
           ("Chair", "headOf", "Department"),
           ("Employee", "worksFor", "Organization"),
           ("TeachingAssistant", "teachingAssistantOf", "Course")]

#: (sub, super) of rdfs:subPropertyOf
SUBPROPERTY = [("headOf", "worksFor"), ("worksFor", "memberOf"),
               ("undergraduateDegreeFrom", "degreeFrom"),
               ("mastersDegreeFrom", "degreeFrom"),
               ("doctoralDegreeFrom", "degreeFrom")]

#: (property, its owl:inverseOf)
INVERSE = [("hasAlumnus", "degreeFrom"), ("member", "memberOf")]

#: owl:TransitiveProperty
TRANSITIVE = ["subOrganizationOf"]

#: rdfs:domain and rdfs:range of the properties the data holds
DOMAIN = {"advisor": "Person", "degreeFrom": "Person",
          "undergraduateDegreeFrom": "Person", "mastersDegreeFrom": "Person",
          "doctoralDegreeFrom": "Person", "emailAddress": "Person",
          "telephone": "Person", "hasAlumnus": "University",
          "member": "Organization", "publicationAuthor": "Publication",
          "subOrganizationOf": "Organization", "teacherOf": "Faculty",
          "teachingAssistantOf": "TeachingAssistant"}
RANGE = {"advisor": "Professor", "degreeFrom": "University",
         "undergraduateDegreeFrom": "University",
         "mastersDegreeFrom": "University",
         "doctoralDegreeFrom": "University", "hasAlumnus": "Person",
         "member": "Person", "publicationAuthor": "Person",
         "subOrganizationOf": "Organization", "teacherOf": "Course",
         "teachingAssistantOf": "Course"}

P = {name: i for i, name in enumerate(PROPERTIES)}


def class_ids(num_asserted_vertices: int) -> Dict[str, int]:
    """Class name -> vertex id: the data's classes keep ``lubm``'s ids,
    the ontology's own follow the asserted vertices."""
    ids = dict(lubm.C)
    for k, name in enumerate(NEW_CLASSES):
        ids[name] = num_asserted_vertices + k
    return ids


@functools.lru_cache(maxsize=4)
def _vertices_drawn(graph_json: str) -> int:
    return lubm.generate_graph(json.loads(graph_json))[3]


def _asserted_vertices(graph: Dict) -> int:
    """Vertex ids ``lubm`` draws for ``graph`` (its last id + 1), drawn
    once per process."""
    return _vertices_drawn(json.dumps(graph, sort_keys=True))


def named_vertices(graph: Dict) -> Dict[str, int]:
    """Every vertex a query may name: the classes, and ``University0``
    (LUBM's queries name the first generated university; a pool of
    ``University`` members cannot, since ``degreeFrom``'s range types
    the degree-only universities too)."""
    named = class_ids(_asserted_vertices(graph))
    named["University0"] = len(lubm.CLASSES)
    return named


def property_names(graph: Dict) -> List[str]:
    return list(PROPERTIES)


def _ancestors() -> Dict[str, List[str]]:
    """Each class's super-classes, transitively (itself excluded)."""
    sup: Dict[str, set] = {c: set() for c in CLASSES}
    for a, b in SUBCLASS:
        sup[a].add(b)
    changed = True
    while changed:
        changed = False
        for c in CLASSES:
            more = set().union(*(sup[b] for b in sup[c])) - sup[c]
            if more:
                sup[c] |= more
                changed = True
    return {c: sorted(v) for c, v in sup.items()}


def _transitive(keys: np.ndarray, nv: int) -> np.ndarray:
    """The transitive closure of the (s, o) pairs ``keys`` encode."""
    while True:
        s, o = keys // nv, keys % nv
        order = np.argsort(s, kind="stable")
        ss, so = s[order], o[order]
        lo = np.searchsorted(ss, o, side="left")
        cnt = np.searchsorted(ss, o, side="right") - lo
        src = np.repeat(np.arange(len(keys)), cnt)
        at = np.repeat(lo - np.cumsum(cnt) + cnt, cnt) + np.arange(cnt.sum())
        grown = np.union1d(keys, s[src] * nv + so[at])
        if len(grown) == len(keys):
            return keys
        keys = grown


def entail(s: np.ndarray, p: np.ndarray, o: np.ndarray, nv: int
           ) -> Tuple[Dict[str, np.ndarray], np.ndarray]:
    """The closure of the asserted triples: (per property other than
    ``type``, its (s, o) pairs encoded ``s * nv2 + o``; a boolean
    membership matrix ``[class, vertex]`` in ``CLASSES`` order), where
    ``nv2`` is ``nv`` plus the ontology's classes."""
    s, p, o = (np.asarray(a, np.int64) for a in (s, p, o))
    nv2 = nv + len(NEW_CLASSES)
    props = {name: np.unique(s[p == i] * nv2 + o[p == i])
             for i, name in enumerate(PROPERTIES) if name != "type"}
    for sub, sup in SUBPROPERTY:
        props[sup] = np.union1d(props[sup], props[sub])
    for a, b in INVERSE:
        both = np.union1d(props[a], (props[b] % nv2) * nv2 + props[b] // nv2)
        props[a] = both
        props[b] = (both % nv2) * nv2 + both // nv2
        props[b].sort()
    for name in TRANSITIVE:
        props[name] = _transitive(props[name], nv2)

    cid = {c: i for i, c in enumerate(CLASSES)}
    vid = class_ids(nv)
    member = np.zeros((len(CLASSES), nv2), bool)
    is_type = p == P["type"]
    for c in lubm.CLASSES:
        member[cid[c], s[is_type & (o == vid[c])]] = True
    for name, c in DOMAIN.items():
        member[cid[c], props[name] // nv2] = True
    for name, c in RANGE.items():
        member[cid[c], props[name] % nv2] = True
    ancestors = _ancestors()
    while True:
        before = int(member.sum())
        for c, sups in ancestors.items():
            for b in sups:
                member[cid[b]] |= member[cid[c]]
        for c, name, filler in DEFINED:
            ps, po = props[name] // nv2, props[name] % nv2
            has = np.zeros(nv2, bool)
            has[ps[member[cid[filler], po]]] = True
            member[cid[c]] |= member[cid["Person"]] & has
        if int(member.sum()) == before:
            return props, member


def generate_graph(graph: Dict) -> Tuple[np.ndarray, np.ndarray,
                                         np.ndarray, int]:
    """(s, p, o, number of vertex ids): LUBM(``universities``, seed)'s
    asserted triples, in ``lubm``'s order, then the entailed ones."""
    s, p, o, nv = lubm.generate_graph(graph)
    props, member = entail(s, p, o, nv)
    nv2 = nv + len(NEW_CLASSES)
    vid = class_ids(nv)
    keys = []
    for c, row in zip(CLASSES, member):
        x = np.flatnonzero(row)
        keys.append((P["type"] * nv2 + x) * nv2 + vid[c])
    for name, pairs in props.items():
        keys.append(P[name] * nv2 * nv2 + pairs)
    entailed = np.unique(np.concatenate(keys))
    asserted = (p.astype(np.int64) * nv2 + s) * nv2 + o
    new = entailed[~np.isin(entailed, asserted)]
    s2, p2, o2 = (new // nv2) % nv2, new // (nv2 * nv2), new % nv2
    return (np.concatenate([s, s2.astype(np.int32)]),
            np.concatenate([p, p2.astype(np.int32)]),
            np.concatenate([o, o2.astype(np.int32)]), nv2)
