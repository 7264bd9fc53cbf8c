"""README-paths drift test: every repository path README.md puts in
backticks (``.py``, ``.json``, ``.md``) exists in the tree. It is what
catches a README whose *Running* section names a file no one runs."""
from tools.gates import missing_readme_paths


def test_every_path_the_readme_names_exists():
    assert missing_readme_paths() == []


def test_a_named_file_that_is_not_in_the_tree_is_reported(tmp_path):
    readme = tmp_path / "README.md"
    readme.write_text(
        "Run `chip_smoke.py` or `no_such_script.py`; see `exec/staging.py`, "
        "`rules.py`, `tools/no_such_tool.py`, `NO_SUCH_RECORD.json` and the "
        "`check_*_docs.py` gates.\n")
    assert missing_readme_paths(str(readme)) == [
        "NO_SUCH_RECORD.json", "no_such_script.py", "tools/no_such_tool.py"]
