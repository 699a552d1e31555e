"""Plain float32 reference of the LMC step, one file per architecture."""
