"""Launch entry points: mesh construction, compile cache, train and serve CLIs."""
