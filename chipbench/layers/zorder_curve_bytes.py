"""The least bytes one launch of the Z-order curve's program
(`ops/zorder.py`, `zorder.curve_perm`) has to move, and of those its
interleave kernel's, from the dispatch record's `attrs` (`columns`,
`n_pad`). Beside `zorder_curve_roofline.py` and
`zorder_interleave_roofline.py`, which read them; no metric's. Whatever
implements the ranks and the sort, these are moved."""

WORD_BYTES = 4      # a key lane, a rank, a key word and a position


def launches(run):
    """The window's dispatch records of the curve that carry their
    shape (a program older than the `attrs` gives none)."""
    return [r["attrs"] for r in run.dispatches
            if r["kernel"] == "zorder.curve_perm"
            and {"columns", "n_pad"} <= set(r.get("attrs", {}))]


def curve_bytes(attrs: dict) -> int:
    """Bound by bytes: the `columns` uint32 key lanes of `n_pad` rows
    are read once and the int32 permutation is written once. The ranks
    and the order are sorts of several passes each, so this is a lower
    bound on the bytes and the share it gives is under 100% by
    construction."""
    return (attrs["columns"] + 1) * WORD_BYTES * attrs["n_pad"]


def interleave_bytes(attrs: dict) -> int:
    """The interleave alone: `columns` rank lanes read once and as many
    key words written once, a few integer operations a bit between."""
    return 2 * attrs["columns"] * WORD_BYTES * attrs["n_pad"]
