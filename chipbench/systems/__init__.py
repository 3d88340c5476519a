"""One module per kind of deployment a configuration file names under
``system``."""
