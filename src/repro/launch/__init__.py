"""Launch stack: the train entry point, the compile cache, the mesh path's
device binding, the process-per-host harness and an HLO cost walker."""
