"""Names under which the system's kernels appear in a device trace."""

#: The batched dense ray-cast Pallas kernel: the custom call that
#: ``repro.kernels.raycast.raycast_count_batch_kernel_call`` lowers to.
RAYCAST_BATCH = "raycast_count_batch_kernel_call"
