"""CPU tests of the benchmark's self-time reduction (bench/phases.py) and of
the per-layer metrics that read it."""
from __future__ import annotations

import collections
import json
import os
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))
sys.path.insert(0, ROOT)

from bench import phases, spec, trace  # noqa: E402

FIXTURE = os.path.join(ROOT, "tests", "bench", "data",
                       "tpu_v5e_tiny_query.json")

LOOP = "jit(_sharded_counts_impl)/jit(run_bucket_persistent)/while/body"
REFILL = LOOP + "/cond/branch_1_fun/engine.refill"
STEAL = LOOP + "/cond/branch_1_fun/engine.steal"
STEP = LOOP + "/vmap(engine.step)"
KERNEL = "/kernels.bitset_ops/pallas_call"


def nested_ops():
    """One chip, one program: while [0, 100) holds a refill cond [10, 50)
    that holds a fusion [15, 25) and a kernel [30, 45); a step kernel
    [55, 90) and a steal op [90, 95) follow; a copy [120, 130) runs after
    the loop. The window is [0, 140)."""
    p = "jit__sharded_counts_impl"
    return {0: [(p, "while.1", 0, 100, LOOP + "/while"),
                (p, "cond.2", 10, 40, LOOP + "/cond"),
                (p, "fusion.3", 15, 10, REFILL + "/and"),
                (p, "frame_step.4", 30, 15, REFILL + KERNEL),
                (p, "frame_step.5", 55, 35, STEP + KERNEL),
                (p, "fusion.6", 90, 5, STEAL + "/select_n"),
                (p, "copy.7", 120, 10, "jit(_sharded_counts_impl)/copy")]}


HOST = [("query", 0, 140), ("driver.dispatch", 0, 5, {"bucket": 0}),
        ("driver.settle", 5, 135, {"bucket": 0, "chunk": 0}),
        ("prep.pack", 100, 15)]


def test_scopes_are_read_inside_transforms():
    assert phases.scopes(STEP + "/jit(k)/add")[-3:] == [
        "engine.step", "k", "add"]
    assert phases.phase_of(LOOP + "/vmap(jvp(engine.steal))/x") == \
        "engine.steal"
    assert phases.phase_of(LOOP + "/engine.stepper/x") == phases.CONTROL
    assert phases.under_kernels("a/vmap(kernels.bitset_ops)/pallas_call")


def test_self_times_add_up_to_the_busy_union():
    iv = [(0, 100), (10, 50), (15, 25), (30, 45), (55, 90), (90, 95),
          (120, 130), (125, 140)]          # the last two overlap, unnested
    own = phases.self_times(iv)
    assert own == [100 - 40 - 35 - 5, 40 - 10 - 15, 10, 15, 35, 5, 5, 15]
    assert sum(own) == sum(e - s for s, e in trace.merge(iv))


def test_nested_phases_add_up_to_busy_and_kernels_count_twice():
    p = phases.reduce(nested_ops(), HOST)
    assert p.busy_ns == 100 + 10 == sum(p.phase_ns.values())
    assert p.phase_ns == {phases.CONTROL: 20 + 15 + 10,
                          "engine.refill": 10 + 15,
                          "engine.steal": 5, "engine.step": 35}
    shares = [p.share(k) for k in (phases.CONTROL,) + phases.PHASES]
    assert sum(shares) == pytest.approx(100.0)
    # the kernel under engine.refill counts toward refill and kernels
    assert p.kernel_ns == 15 + 35
    assert p.kernel_share() == pytest.approx(100.0 * 50 / 110)
    assert p.scoped and p.has_kernels and p.n_ops == 7
    assert p.program_ns == {"jit__sharded_counts_impl": 110}


def test_idle_is_named_by_the_innermost_program_span():
    p = phases.reduce(nested_ops(), HOST)
    # idle [100, 120) lies under prep.pack inside driver.settle, [130, 140)
    # under driver.settle alone
    assert p.stage_idle == {"prep.pack": 20, "driver.settle": 10}
    host = [("query", 0, 140), ("PjitFunction(step)", 95, 45)]
    assert phases.reduce(nested_ops(), host).stage_idle == {
        phases.NO_SPAN: 30}


def test_programs_sharing_an_instruction_name_are_attributed_apart():
    """`fusion.9` is a refill op in one chunk program and a steal op in
    another; each event takes its own program's path."""
    ops = {0: [("prog_u32", "fusion.9", 0, 10, REFILL + "/and"),
               ("prog_u64", "fusion.9", 20, 30, STEAL + "/and")]}
    p = phases.reduce(ops, [("query", 0, 50)])
    assert p.phase_ns == {"engine.refill": 10, "engine.steal": 30}
    assert p.program_ns == {"prog_u32": 10, "prog_u64": 30}


def test_a_program_without_scopes_reads_as_unscoped():
    ops = {0: [("p", "while.1", 0, 10, "jit(f)/while"),
               ("p", "fusion.2", 2, 5, "jit(f)/while/body/and")]}
    p = phases.reduce(ops, [("query", 0, 10)])
    assert not p.scoped and not p.has_kernels
    assert p.share(phases.CONTROL) == 100.0
    assert phases.reduce(ops, []) is None and phases.reduce({}, HOST) is None


def test_extract_keys_each_op_by_its_own_program():
    """An op event names its program by its stats, or failing that by the
    module event that spans it; its path is its first path stat, or failing
    that its instruction's op_name in that program's HLO."""
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": phases.MODULES_LINE, "events": [
            ["jit_a(1)", 0, 50, {}], ["jit_b(2)", 60, 40, {}]]},
        {"name": trace.OPS_LINE, "events": [
            ["fusion.1", 5, 10, {"tf_op": REFILL + "/and"}],
            ["fusion.1", 70, 10, {"tf_op": STEAL + "/and",
                                  "program_id": 2}],
            ["%copy.2 = u32[8]{0} copy(u32[8]{0} %p)", 80, 5, {}],
            ["copy.3", 85, 5, {}]]}]}
    host = {"name": trace.HOST_PLANE, "lines": [
        {"name": "python", "events": [["query", 0, 100, {}],
                                      ["driver.settle", 50, 50,
                                       {"bucket": 1, "chunk": 3}]]}]}
    other = {"name": "/device:TPU:1", "lines": []}
    hlo = {2: {"copy.2": STEP + "/copy"}, 1: {"copy.3": STEAL + "/copy"}}
    ops, hst = phases.extract([dev, host, other], [0], hlo)
    assert ops == {0: [
        ("jit_a(1)", "fusion.1", 5, 10, REFILL + "/and"),
        ("jit_b(2)", "fusion.1", 70, 10, STEAL + "/and"),
        ("jit_b(2)", "%copy.2 = u32[8]{0} copy(u32[8]{0} %p)", 80, 5,
         STEP + "/copy"),
        ("jit_b(2)", "copy.3", 85, 5, None)]}
    assert hst == [("query", 0, 100), ("driver.settle", 50, 50)]
    cpu = {"hlo_op": "copy.9", "hlo_module": "jit_f", "program_id": 7}
    assert phases._program(cpu, [], 0, 1) == (7, "jit_f(7)")


def recorded() -> dict:
    """The recorded chip trace, its tf_op strings put back in place."""
    with open(FIXTURE) as f:
        fx = json.load(f)
    for plane in fx["planes"]:
        for line in plane["lines"]:
            for ev in line["events"]:
                if phases.PATH_KEY in ev[3]:
                    ev[3][phases.PATH_KEY] = fx["paths"][ev[3][phases.PATH_KEY]]
    fx["op_names"] = {int(k): v for k, v in fx["op_names"].items()}
    return fx


def test_recorded_chip_trace_carries_the_op_path_in_tf_op():
    """On the chip an op event's metadata holds its scope path in `tf_op`
    as `<op_name>:<op type>`, and its program in `program_id`; the few ops
    without a `tf_op` (async copy and slice halves) take their HLO
    op_name. Self times add up to the busy union, every phase shows, and
    an instruction name that both programs use is attributed by each
    program's own path."""
    fx = recorded()
    dev, = (p for p in fx["planes"] if trace.DEVICE_PLANE.match(p["name"]))
    events = {ln["name"]: ln["events"] for ln in dev["lines"]}[trace.OPS_LINE]
    tagged = [ev[3][phases.PATH_KEY] for ev in events
              if phases.PATH_KEY in ev[3]]
    assert len(tagged) > 0.8 * len(events)
    assert all(t.endswith(":") for t in tagged)
    assert all("program_id" in ev[3] for ev in events)

    ops, host = phases.extract(fx["planes"], [0], fx["op_names"])
    assert sum(1 for r in ops[0] if r[4]) > len(tagged)
    p = phases.reduce(ops, host)
    assert p.scoped and p.has_kernels and p.n_ops == len(events)
    assert set(p.phase_ns) == {phases.CONTROL, *phases.PHASES}
    assert sum(p.share(k) for k in p.phase_ns) == pytest.approx(100.0)
    lo = min(s for n, s, d in host if n == trace.QUERY_SPAN)
    hi = max(s + d for n, s, d in host if n == trace.QUERY_SPAN)
    union = trace.clip(trace.merge([(s, s + d) for _, _, s, d, _ in ops[0]]),
                       lo, hi)
    assert p.busy_ns == pytest.approx(sum(e - s for s, e in union))
    assert len(p.program_ns) == 2
    paths = collections.defaultdict(dict)
    for prog, name, _, _, path in ops[0]:
        paths[name][prog] = path
    assert any(len(set(v.values())) == 2 for v in paths.values())


class _Ctx:
    def __init__(self, p, traced=True):
        self.trace = object() if traced else None
        self.phases = p
        self.queries = []
        self.chips = 1


@pytest.mark.parametrize("name,want", [
    ("engine.control_share", 100.0 * 45 / 110),
    ("engine.refill_share", 100.0 * 25 / 110),
    ("engine.steal_share", 100.0 * 5 / 110),
    ("kernels.busy_share", 100.0 * 50 / 110)])
def test_phase_readers(name, want):
    read = spec.load_metric(name).read
    assert read(_Ctx(phases.reduce(nested_ops(), HOST))) == \
        pytest.approx(want)
    assert read(_Ctx(None, traced=False)) is None
    bare = {0: [("p", "while.1", 0, 10, "jit(f)/while")]}
    assert read(_Ctx(phases.reduce(bare, [("query", 0, 10)]))) is None
