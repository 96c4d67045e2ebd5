"""Writes ``programs.xplane.pb``: a hand-made profiler trace in the layout
a TPU run of the serving engine records, two engine iterations of named
programs (a ``/device:TPU:0`` plane with ``XLA Ops`` and ``XLA Modules``
lines, a ``/host:CPU`` plane with the harness's ``wave`` span and the
engine's nested program spans with their stats), whose per-program
device times and idle times are known exactly. Run it with JAX
installed:

    python3 perfbench/fixtures/make_programs.py
"""
from pathlib import Path

from jax.profiler import ProfileData

# microseconds: (program as the module line names it, start, duration);
# each program's operations fill its module's interval, and the last one
# runs 5 us into the window before the window ends
MODULES = [
    ("jit_cache_scrub(11)", 30, 70),
    ("jit_serve_decode(12)", 160, 230),
    ("jit_cache_encode(13)", 430, 40),
    ("jit_cache_scrub(11)", 540, 50),
    ("jit_serve_prefill(14)", 620, 70),
    ("jit_serve_decode(15)", 720, 170),
    ("jit_cache_encode(13)", 920, 50),
    ("jit_graph_scrub_slice(16)", 995, 25),
]
# (name, start, duration, stats)
HOST = [
    ("wave", 0, 1000, {}),
    ("serve.iteration", 10, 490, {"step_num": 0}),
    ("serve.kv_check", 20, 100, {"pages": 50}),
    ("serve.decode", 150, 250, {"active": 2}),
    ("serve.kv_refresh", 420, 60, {"pages": 50}),
    ("serve.iteration", 520, 470, {"step_num": 1}),
    ("serve.kv_check", 530, 70, {"pages": 50}),
    ("serve.prefill", 610, 90, {"rid": 0, "prompt_len": 20, "pages": 3}),
    ("serve.decode", 710, 190, {"active": 3}),
    ("serve.kv_refresh", 910, 70, {"pages": 50}),
]


def text_proto() -> str:
    names = sorted({m[0] for m in MODULES} | {h[0] for h in HOST}
                   | {f"fusion.{i}" for i in range(len(MODULES))})
    ids = {n: i + 1 for i, n in enumerate(names)}
    stat_names = sorted({k for h in HOST for k in h[3]})
    sids = {n: i + 1 for i, n in enumerate(stat_names)}
    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}' for n, i in ids.items())
    meta += "".join(f' stat_metadata {{ key: {i} value {{ id: {i} '
                    f'name: "{n}" }} }}' for n, i in sids.items())

    def event(name, start, dur, stats=None):
        st = "".join(f' stats {{ metadata_id: {sids[k]} int64_value: {v} }}'
                     for k, v in (stats or {}).items())
        return (f' events {{ metadata_id: {ids[name]} offset_ps: '
                f'{start * 10 ** 6} duration_ps: {dur * 10 ** 6}{st} }}')

    ops = "".join(event(f"fusion.{i}", a, d)
                  for i, (_, a, d) in enumerate(MODULES))
    mods = "".join(event(n, a, d) for n, a, d in MODULES)
    host = "".join(event(*h) for h in HOST)
    return (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0{ops} }} lines {{ id: 2 '
            f'name: "XLA Modules" timestamp_ns: 0{mods} }}{meta} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 '
            f'name: "python3" timestamp_ns: 0{host} }}{meta} }}')


if __name__ == "__main__":
    raw = ProfileData.text_proto_to_serialized_xspace(text_proto())
    (Path(__file__).resolve().parent / "programs.xplane.pb").write_bytes(raw)
