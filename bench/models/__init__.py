"""Reference models and how the program is configured for them."""
