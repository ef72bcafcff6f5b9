"""Found by file name (harness.loader.plugin); one file per entry."""
