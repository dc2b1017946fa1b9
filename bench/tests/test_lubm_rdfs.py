"""The entailed LUBM graph against a naive fixpoint of the univ-bench
rules, written out again here one rule at a time over a set of
triples, on a one-department university."""
import json

import numpy as np
import pytest

from harness import lubm, lubm_rdfs, registry

SUBCLASS = {
    "FullProfessor": ["Professor"], "AssociateProfessor": ["Professor"],
    "AssistantProfessor": ["Professor"], "Chair": ["Professor", "Person"],
    "Professor": ["Faculty"], "Lecturer": ["Faculty"],
    "Faculty": ["Employee"], "Employee": ["Person"],
    "UndergraduateStudent": ["Student"], "Student": ["Person"],
    "GraduateStudent": ["Person"], "TeachingAssistant": ["Person"],
    "GraduateCourse": ["Course"], "Course": ["Work"],
    "University": ["Organization"], "Department": ["Organization"],
    "ResearchGroup": ["Organization"]}
DEFINED = [("Student", "takesCourse", "Course"),
           ("Chair", "headOf", "Department"),
           ("Employee", "worksFor", "Organization"),
           ("TeachingAssistant", "teachingAssistantOf", "Course")]
SUBPROPERTY = {"headOf": "worksFor", "worksFor": "memberOf",
               "undergraduateDegreeFrom": "degreeFrom",
               "mastersDegreeFrom": "degreeFrom",
               "doctoralDegreeFrom": "degreeFrom"}
INVERSE = {"hasAlumnus": "degreeFrom", "degreeFrom": "hasAlumnus",
           "member": "memberOf", "memberOf": "member"}
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


def naive_closure(triples, prop, cls):
    """Apply every rule to every triple until nothing new comes out."""
    name = {v: k for k, v in prop.items()}
    typ, sub_org = prop["type"], prop["subOrganizationOf"]
    triples = set(triples)
    while True:
        new = set()
        typed = {(s, o) for s, p, o in triples if p == typ}
        for s, p, o in triples:
            n = name[p]
            if p == typ:
                for c, sups in SUBCLASS.items():
                    if o == cls[c]:
                        new |= {(s, typ, cls[sup]) for sup in sups}
            if n in SUBPROPERTY:
                new.add((s, prop[SUBPROPERTY[n]], o))
            if n in INVERSE:
                new.add((o, prop[INVERSE[n]], s))
            if n in DOMAIN:
                new.add((s, typ, cls[DOMAIN[n]]))
            if n in RANGE:
                new.add((o, typ, cls[RANGE[n]]))
            if p == sub_org:
                new |= {(s, sub_org, o2) for s2, p2, o2 in triples
                        if p2 == sub_org and s2 == o}
            for c, via, filler in DEFINED:
                if (n == via and (s, cls["Person"]) in typed
                        and (o, cls[filler]) in typed):
                    new.add((s, typ, cls[c]))
        if new <= triples:
            return triples
        triples |= new


@pytest.fixture(scope="module")
def one_department():
    """The new configuration's graph, one university of one department."""
    config = registry.cell("lubm-rdfs-1chip.c16")["config"]
    graph = config["graph"]
    return dict(graph, universities=1, profile=dict(
        graph["profile"], departments_per_university=[1, 1]))


def test_closure_is_the_naive_fixpoint(one_department):
    s, p, o, nv = lubm.generate_graph(one_department)
    s2, p2, o2, nv2 = lubm_rdfs.generate_graph(one_department)
    # the asserted triples come first, unchanged, with their vertex ids
    n = len(s)
    for a, b in ((s, s2), (p, p2), (o, o2)):
        assert np.array_equal(a, b[:n])
    assert nv2 == nv + len(lubm_rdfs.NEW_CLASSES)
    prop = {name: i for i, name in enumerate(
        lubm_rdfs.property_names(one_department))}
    cls = lubm_rdfs.class_ids(nv)
    want = naive_closure(zip(s.tolist(), p.tolist(), o.tolist()), prop, cls)
    got = set(zip(s2.tolist(), p2.tolist(), o2.tolist()))
    assert len(got) == len(s2)          # each triple once
    assert got == want
    assert len(got) > 1.2 * n           # the closure adds real work


def test_named_vertices(one_department):
    named = lubm_rdfs.named_vertices(one_department)
    s, p, o, nv = lubm_rdfs.generate_graph(one_department)
    assert set(named) == set(lubm_rdfs.CLASSES) | {"University0"}
    assert sorted(named[c] for c in lubm_rdfs.NEW_CLASSES) \
        == list(range(nv - len(lubm_rdfs.NEW_CLASSES), nv))
    typ = lubm_rdfs.P["type"]
    u0 = named["University0"]
    assert {int(c) for c in o[(s == u0) & (p == typ)]} \
        == {named["University"], named["Organization"]}
    # the answers of a query over an inferred class and property: every
    # graduate and undergraduate taking a course is a Student
    students = set(s[(p == typ) & (o == named["Student"])].tolist())
    takers = set(s[p == lubm_rdfs.P["takesCourse"]].tolist())
    assert takers and takers <= students


def test_mix_names_only_what_the_generator_has():
    spec = registry.cell("lubm-rdfs-1chip.c16")
    mix, graph = spec["mix"], spec["config"]["graph"]
    props = set(lubm_rdfs.property_names(graph))
    named = set(lubm_rdfs.CLASSES) | {"University0"}
    assert [sh["name"] for sh in mix["shapes"]] \
        == [f"Q{i}" for i in range(1, 15)]
    for sh in mix["shapes"]:
        for a, b, prop in sh["edges"]:
            assert prop in props
            assert all(isinstance(v, int) or v in named for v in (a, b))
        if sh["bind"] is not None:
            assert sh["bind"]["other"] in named
    assert json.loads(json.dumps(mix)) == mix
