"""Device dispatch funnel: host-to-device megabytes of the window's
`stats.index_upload` dispatch records, per refresh: what of the index
crosses to the chip each time a commit lands (lanes x padded files x 8
bytes, and the validity words). None where the window uploaded no
index. `bids_index_upload_mb`'s reading, over this cell's refreshes."""

from chipbench.layers.bids_index_upload_mb import read  # noqa: F401
