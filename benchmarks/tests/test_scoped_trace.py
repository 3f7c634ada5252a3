"""``harness/scoped_trace.py`` on a trace written by hand in the protobuf
wire format it reads: two programs whose instructions carry ``op_name``
metadata in the ``/host:metadata`` plane, whole executions and stubs of
them on the device plane."""

import toy  # noqa: F401  (puts benchmarks/ on the path)
from harness import scoped_trace


def _varint(n):
    out = bytearray()
    while True:
        out.append((n & 0x7F) | (0x80 if n > 0x7F else 0))
        n >>= 7
        if not n:
            return bytes(out)


def _field(number, value):
    if isinstance(value, int):
        return _varint(number << 3) + _varint(value)
    if isinstance(value, str):
        value = value.encode()
    return _varint(number << 3 | 2) + _varint(len(value)) + value


def _msg(*fields):
    return b"".join(_field(n, v) for n, v in fields)


def _hlo(program, instructions):
    """An HloProto holding one computation of named instructions."""
    computation = _msg((1, "main"), *[
        (2, _msg((1, name), (2, "fusion"), *([(7, _msg((2, op_name)))] if op_name else [])))
        for name, op_name in instructions
    ])
    return _msg((1, _msg((1, program), (3, computation))))


def _metadata_plane(programs):
    entries = [
        (4, _msg((1, i), (2, _msg(
            (1, i), (2, f"{program}({1000 + i})"),
            (5, _msg((1, 1), (6, _hlo(program, instructions)))),
        ))))
        for i, (program, instructions) in enumerate(programs.items(), 1)
    ]
    return _msg((2, "/host:metadata"), *entries)


def _device_plane(modules, ops):
    """Events as (name, start us, duration us)."""
    names = sorted({n for n, _, _ in modules + ops})
    ids = {n: i for i, n in enumerate(names, 1)}

    def line(name, events):
        return _msg((2, name), (3, 5), *[  # the line starts at 5 ns
            (4, _msg((1, ids[n]), (2, s * 1_000_000), (3, d * 1_000_000)))
            for n, s, d in events
        ])

    return _msg(
        (2, "/device:TPU:0"),
        *[(4, _msg((1, i), (2, _msg((1, i), (2, n))))) for n, i in ids.items()],
        (3, line("XLA Modules", modules)), (3, line("XLA Ops", ops)),
    )


DECODE = "jit__paged_decode_chunk"
PROGRAMS = {
    DECODE: [
        ("fusion.1", f"jit(_paged_decode_chunk)/while/body/mla_absorbed/gather"),
        ("gmm.2", "jit(_paged_decode_chunk)/while/body/moe_dispatch/moe_experts/jit(gmm)/pallas_call"),
        ("fusion.3", "jit(_paged_decode_chunk)/while/body/moe_dispatch/sort"),
        ("fusion.4", "jit(_paged_decode_chunk)/while/body/dot_general"),
        ("while.9", None),
    ],
    "jit__paged_prefill_prog": [
        ("fusion.1", "jit(_paged_prefill_prog)/mla_materialised/dot_general"),
    ],
}


def _op(name):
    return f"%{name} = f32[8]{{0}} fusion(f32[8]{{0}} %p), kind=kLoop"


def _trace(tmp_path):
    # a stub of the decode program where the trace starts, one whole
    # execution of two steps, a prefill call, and a stub where it stops
    modules = [
        (f"{DECODE}(1001)", 0, 50), (f"{DECODE}(1001)", 100, 400),
        ("jit__paged_prefill_prog(1002)", 520, 30), (f"{DECODE}(1001)", 600, 100),
    ]
    step = lambda at: [  # noqa: E731
        (_op("fusion.1"), at, 100), (_op("gmm.2"), at + 100, 20),
        (_op("fusion.3"), at + 120, 5), (_op("fusion.3"), at + 125, 5),
        (_op("fusion.4"), at + 130, 40),
    ]
    ops = (
        [(_op("fusion.1"), 0, 50)]
        + [("%while.9 = (s32[]) while((s32[]) %t), body=%b", 100, 400)]
        + step(100) + step(300)
        + [(_op("fusion.1"), 520, 30), (_op("fusion.1"), 600, 100)]
    )
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(
        _msg((1, _metadata_plane(PROGRAMS)), (1, _device_plane(modules, ops)))
    )
    return str(path)


MARKERS = ("mla_absorbed", "mla_materialised", "moe_dispatch", "moe_experts")


def test_instruction_scopes_come_from_the_metadata_plane():
    scopes = scoped_trace.instruction_scopes(_metadata_plane(PROGRAMS))
    assert scopes[DECODE]["gmm.2"].endswith("moe_experts/jit(gmm)/pallas_call")
    assert "while.9" not in scopes[DECODE]
    assert scopes["jit__paged_prefill_prog"]["fusion.1"].startswith("jit(_paged_prefill_prog)")


def test_seconds_by_scope_over_whole_executions_alone(tmp_path):
    table = scoped_trace.reduce(_trace(tmp_path), MARKERS)
    decode = table[DECODE]
    assert decode["whole_executions"] == 1 and decode["steps"] == 2
    # the container's 400 us are its children's: 2 x (100 + 20 + 5 + 5 + 40)
    assert decode["device_s"] * 1e6 == 340
    # the innermost marker of a path counts; fusion.4 names none; the
    # stubs' 50 and 100 us of fusion.1 are not in
    scopes = {k: round(v * 1e6) for k, v in decode["scopes"].items()}
    assert scopes == {"mla_absorbed": 200, "moe_experts": 40, "moe_dispatch": 20}
    # the same instruction name in another program is that program's
    prefill = table["jit__paged_prefill_prog"]
    assert prefill["whole_executions"] == 1 and prefill["steps"] == 1
    assert {k: round(v * 1e6) for k, v in prefill["scopes"].items()} == {"mla_materialised": 30}


def test_a_trace_without_the_metadata_gives_empty_scopes(tmp_path):
    path = tmp_path / "bare.xplane.pb"
    path.write_bytes(_msg((1, _device_plane(
        [(f"{DECODE}(1)", 10, 100)], [(_op("fusion.1"), 0, 5), (_op("fusion.1"), 10, 100), (_op("fusion.1"), 200, 5)],
    ))))
    table = scoped_trace.reduce(str(path), MARKERS)
    assert table[DECODE]["scopes"] == {} and table[DECODE]["steps"] == 1
    empty = tmp_path / "empty.xplane.pb"
    empty.write_bytes(b"")
    assert scoped_trace.reduce(str(empty), MARKERS) == {}
