"""Plain float32 reference forwards, one module per model."""
