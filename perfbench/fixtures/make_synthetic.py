"""Writes ``synthetic.xplane.pb``: a hand-made profiler trace in the
layout a TPU run records (a ``/device:TPU:0`` plane with an ``XLA Ops``
line, a ``/host:CPU`` plane with the harness's spans), whose busy time,
kernel times and idle gaps are known exactly. Run it with JAX installed:

    python3 perfbench/fixtures/make_synthetic.py
"""
from pathlib import Path

from jax.profiler import ProfileData

# microseconds: (name, start, duration, jitted wrapper of the kernel or None);
# a kernel's operation is named after the function that wraps its pallas_call
DEVICE_OPS = [
    ("fusion.1", 10, 100, None),
    ("secded_scrub_words.2", 120, 60, "secded_scrub_words"),
    ("parity_encode_words.3", 190, 40, "parity_encode_words"),
    ("parity_check_words.4", 300, 30, "parity_check_words"),
    ("convolution.5", 400, 300, None),
    ("edge_segment_push_blocked.6", 1150, 200, "edge_segment_push_blocked"),
    ("edge_segment_push_blocked.6", 1380, 200, "edge_segment_push_blocked"),
]
HOST = [("wave", 0, 1000), ("PjitFunction(step)", 5, 10),
        ("PjitFunction(fn)", 110, 5), ("block_until_ready", 720, 270),
        ("pagerank_chunk", 1100, 500), ("PjitFunction(push)", 1105, 40)]


def text_proto() -> str:
    names = sorted({o[0] for o in DEVICE_OPS} | {h[0] for h in HOST})
    ids = {n: i + 1 for i, n in enumerate(names)}
    meta = "".join(f' event_metadata {{ key: {i} value {{ id: {i} '
                   f'name: "{n}" }} }}' for n, i in ids.items())
    meta += (' stat_metadata { key: 1 value { id: 1 name: "hlo_module" } }'
             ' stat_metadata { key: 2 value { id: 2 name: "long_name" } }')

    def event(name, start, dur, kernel=None, module=True):
        stats = ' stats { metadata_id: 1 str_value: "jit_fn" }' \
            if module else ""
        if kernel:
            stats += (f' stats {{ metadata_id: 2 str_value: "%{name} = '
                      f'custom-call(), op_name=jit({kernel})/pallas_call" }}')
        return (f' events {{ metadata_id: {ids[name]} offset_ps: '
                f'{start * 10 ** 6} duration_ps: {dur * 10 ** 6}{stats} }}')

    dev = "".join(event(*o) for o in DEVICE_OPS)
    host = "".join(event(*h, module=False) for h in HOST)
    return (f'planes {{ id: 1 name: "/device:TPU:0" lines {{ id: 1 '
            f'name: "XLA Ops" timestamp_ns: 0{dev} }} lines {{ id: 2 '
            f'name: "XLA Modules" timestamp_ns: 0 }}{meta} }} '
            f'planes {{ id: 2 name: "/host:CPU" lines {{ id: 1 '
            f'name: "python3" timestamp_ns: 0{host} }}{meta} }}')


if __name__ == "__main__":
    raw = ProfileData.text_proto_to_serialized_xspace(text_proto())
    (Path(__file__).resolve().parent / "synthetic.xplane.pb").write_bytes(raw)
