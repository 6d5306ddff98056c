"""How ``lm_scoped_slice.xplane.pb`` was made, so that it can be made again.

On the chip (``record``): the program's ``DistributedLMTrainer`` at GPT-2
medium's widths (1024 wide, 16 heads, vocabulary 50257, B=8 x T=1024, bf16,
remat ``full``) cut to two blocks, so that two whole steps with every scope
fit a test file; four steps to warm up, three under a profiler session.
Anywhere (``trim``): the first two steps of that trace, cut down to what
``scope_reduce.py`` reads - the TPU plane's ``XLA Ops`` events with their
metadata's ``tf_op``, and the host's ``fedml:`` spans.

    python benchmark/testdata/record_lm_scoped_slice.py record <dir>
    python benchmark/testdata/record_lm_scoped_slice.py trim <dir or .xplane.pb> <out.xplane.pb>
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
NAME_CHARS = 100  # an instruction's text starts with its unique name
STEPS = 2


def record(out_dir: str) -> None:
    sys.path.insert(0, os.path.dirname(os.path.dirname(HERE)))
    import jax
    import jax.numpy as jnp
    import numpy as np

    from fedml_tpu.parallel.trainer import DistributedLMTrainer, DistTrainConfig

    trainer = DistributedLMTrainer(
        DistTrainConfig(use_remat=True, remat_policy="full"),
        vocab_size=50257, dim=1024, num_heads=16, num_layers=2, max_len=1024,
        dtype=jnp.bfloat16)
    ids = np.random.default_rng(0).integers(0, 50257, (8, 1025), dtype=np.int32)
    for _ in range(4):
        trainer.step(ids[:, :-1], ids[:, 1:])
    jax.profiler.start_trace(out_dir)
    for _ in range(3):
        trainer.step(ids[:, :-1], ids[:, 1:])
    jax.profiler.stop_trace()


def _quoted(text: str) -> str:
    return '"' + text.replace("\\", "\\\\").replace('"', '\\"') + '"'


def plane_text(plane_id: int, name: str, line_name: str, rows,
               tf_ops: dict | None = None) -> str:
    """One XPlane in text form, with one line of events from ``rows``
    (start_ns, duration_ns, event name); ``tf_ops`` gives each event's
    metadata its ``tf_op`` stat."""
    ids: dict = {}
    events = []
    for start, dur, ev_name in rows:
        md = ids.setdefault(ev_name, len(ids) + 1)
        events.append(f"events {{ metadata_id: {md} offset_ps: {start * 1000} "
                      f"duration_ps: {dur * 1000} }}")
    metadata = []
    for ev_name, md in ids.items():
        stat = ("" if tf_ops is None else " stats { metadata_id: 1 str_value: "
                + _quoted(tf_ops.get(ev_name, "")) + " }")
        metadata.append(f"event_metadata {{ key: {md} value {{ id: {md} name: "
                        f"{_quoted(ev_name[:NAME_CHARS])}{stat} }} }}")
    return (f"planes {{ id: {plane_id} name: {_quoted(name)} lines {{ id: 1 "
            f"name: {_quoted(line_name)} {' '.join(events)} }} "
            f"{' '.join(metadata)} stat_metadata {{ key: 1 value {{ id: 1 "
            f'name: "tf_op" }} }} }}')


def trim(src: str, dst: str) -> None:
    from jax.profiler import ProfileData

    import scope_reduce as sr

    path = sr.find_xplane(src)
    device, host = None, []
    for plane in ProfileData.from_file(path).planes:
        if plane.name.startswith(sr.DEVICE_PREFIX) and device is None:
            line = next(l for l in plane.lines if l.name == sr.OPS_LINE)
            device = (plane.name, [(int(e.start_ns), int(e.duration_ns), e.name)
                                   for e in line.events])
        elif not plane.name.startswith(sr.DEVICE_PREFIX):
            host += [(int(e.start_ns), int(e.duration_ns), e.name)
                     for l in plane.lines for e in l.events
                     if e.name.startswith(sr.PROGRAM_PREFIX)]
    host.sort()
    steps = [(s, s + d) for s, d, n in host if n == sr.STEP_SPAN][:STEPS]
    t0, t1 = steps[0][0], steps[-1][1]
    kept = lambda rows: [(s - t0, d, n) for s, d, n in rows  # noqa: E731
                         if t0 <= s and s + d <= t1]
    text = "\n".join([
        plane_text(1, device[0], sr.OPS_LINE, kept(device[1]),
                   sr.event_op_names(path)[device[0]]),
        plane_text(2, "/host:CPU", "python3", kept(host))])
    with open(dst, "wb") as f:
        f.write(ProfileData.text_proto_to_serialized_xspace(text))


if __name__ == "__main__":
    {"record": record, "trim": trim}[sys.argv[1]](*sys.argv[2:])
