"""Plain float32 reference models, one module per architecture."""
