"""Seconds leg 2 of a cycle spent rebuilding the device's state from the
frame (``restore_s``: ``DeviceChecker._restore_frame``, inside the
``init`` phase).  Median over the window's cycles; None on a commit
without the part counters.  Prints, by cycle, its three parts
(``restore_load_s``: the frame read and decompressed;
``restore_unpack_s``: the table's columns rebuilt and the buffers padded
on the host; ``restore_upload_s``: the upload, waited for), the bytes
uploaded, and what the run resumed from."""

from benchmark.lib import program_spans, sweep_bytes


def read(ctx, params):
    for i, a in enumerate(ctx["out"]["answers"]):
        st = a.get("stats", {})
        if "restore_upload_s" not in st:
            continue
        program_spans.say(
            f"cycle {i}: restore {st['restore_s']:.4f} s = load "
            f"{st['restore_load_s']:.4f} + unpack "
            f"{st['restore_unpack_s']:.4f} + upload "
            f"{st['restore_upload_s']:.4f}; "
            f"{st['restore_h2d_bytes'] / 1e9:.4f} GB uploaded; resumed at "
            f"level {st['resume_level']} with {st['resume_states']} states, "
            f"{st['resume_levels_run']} levels run after it")
    return sweep_bytes.median_over_checks(
        ctx, lambda st: st["restore_s"] if "restore_upload_s" in st else None)
