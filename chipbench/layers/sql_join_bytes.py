"""The least bytes one launch of a join's sort program (`ops/sqlops.py`)
has to move, from its dispatch record's `attrs`. Beside
`sql_join_roofline.py` and `sql_join_codes_roofline.py`, which read it;
whatever implements the sort, these are moved."""

KEY_BYTES = 8       # a value lane is int64
CODE_BYTES = 4      # a code lane is uint32
PERM_BYTES = 4      # the order comes back as int32 positions
FIRST_BYTES = 1     # and a flag where the key changes


def join_lanes_bytes(attrs: dict) -> int:
    """Bound by bytes: both padded key lanes are read once, and one
    position and one flag a padded row are written once. A sort is
    several passes over its lane, so this is a lower bound on the bytes
    and the share it gives is under 100% by construction."""
    rows = attrs["nl_pad"] + attrs["nr_pad"]
    return rows * (KEY_BYTES + PERM_BYTES + FIRST_BYTES)


def join_codes_bytes(attrs: dict) -> int:
    """As `join_lanes_bytes`, over the one concatenated lane of codes."""
    return attrs["n_pad"] * (CODE_BYTES + PERM_BYTES + FIRST_BYTES)
