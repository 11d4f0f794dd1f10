"""Seconds of a cycle its checks spent in the ``ckpt`` phase of their
``PhaseClock`` (``host_ckpt_s``, both legs added): the frames' stall on
the run loop's thread.  Median over the window's cycles; None on a
commit without the part counters.  Prints, by cycle, the three parts
that add up to it (``ckpt_gather_s``: both table columns whole and the
rows and logs from the device; ``ckpt_pack_s``: the occupied slots
picked out on the host; ``ckpt_npz_s``: the compressed write), the
frames, the states in them, their bytes a state before and after
compression against what ``benchmark/lib/ckpt_bytes.py`` reckons, the
bytes that crossed the link, and each leg's wall."""

from benchmark.lib import ckpt_bytes, program_spans, sweep_bytes


def read(ctx, params):
    shapes = ctx["config"]["shapes"]
    for i, a in enumerate(ctx["out"]["answers"]):
        st = a.get("stats", {})
        if "ckpt_npz_s" not in st or not st.get("ckpt_states"):
            continue
        n = st["ckpt_states"]
        reckoned = ckpt_bytes.frame_bytes(
            n, shapes["key_columns"], shapes["state_words"])
        program_spans.say(
            f"cycle {i}: frames' stall {st['host_ckpt_s']:.4f} s = gather "
            f"{st['ckpt_gather_s']:.4f} + pack {st['ckpt_pack_s']:.4f} + "
            f"npz {st['ckpt_npz_s']:.4f} (+ "
            f"{st['host_ckpt_s'] - st['ckpt_gather_s'] - st['ckpt_pack_s'] - st['ckpt_npz_s']:.4f}"
            f" around them); {st['ckpt_frames']} frames of {n} states: "
            f"{st['ckpt_raw_bytes'] / n:.2f} B a state raw "
            f"({reckoned / n:.2f} reckoned), {st['ckpt_bytes'] / n:.2f} "
            f"compressed (ratio {st['ckpt_raw_bytes'] / st['ckpt_bytes']:.3f}"
            f"), {st['ckpt_d2h_bytes'] / 1e9:.4f} GB over the link; legs "
            + " + ".join(f"{leg['wall_s']:.3f}" for leg in a.get("legs", ()))
            + " s")
    return sweep_bytes.median_over_checks(
        ctx, lambda st: st["host_ckpt_s"] if "ckpt_npz_s" in st else None)
