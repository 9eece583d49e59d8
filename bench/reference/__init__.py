"""Plain references the served models are held to."""
