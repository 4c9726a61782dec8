"""Pallas TPU kernels (validated in interpret mode on CPU):
cms/ — batched TinyLFU count-min sketch (the paper's data structure);
admission — device-resident admission decisions: the victim walks and
verdict replay of every device program, and the per-decision kernels of
``data_plane="device_full"``'s host path;
device_full — the whole simulation step as one ``lax.scan`` per chunk;
fleet — many ``device_full`` caches per launch;
attention/ — flash attention forward (+jnp VJP);
wkv/ — RWKV6 chunked linear recurrence."""
